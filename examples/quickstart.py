"""Quickstart: train a reduced model for a few steps, then serve it with the
Jenga-managed engine. Run: PYTHONPATH=src python examples/quickstart.py"""
from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving import Engine, EngineConfig, Request, SamplingParams
from repro.training import AdamWConfig, SyntheticLM, Trainer, TrainerConfig


def main():
    use_compile_cache()
    cfg = reduced(ARCHS["granite-3-2b"])
    model = build_model(cfg, single_device_dist())

    print("== train a few steps (AdamW, NaN watchdog, async checkpoints) ==")
    trainer = Trainer(model, AdamWConfig(lr=1e-2, warmup_steps=5),
                      TrainerConfig(ckpt_dir="/tmp/quickstart_ckpt",
                                    ckpt_every=10, micro_batches=2))
    params, state = trainer.init_state(0)
    data = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=8)
    params, state, hist = trainer.run(
        params, state, data, num_steps=20,
        on_metrics=lambda s, m: print(f"  step {s}: loss={m['loss']:.3f}"))
    print(f"  loss {hist[0]:.3f} -> {hist[-1]:.3f}")

    print("== serve with the Jenga KV manager (prefix caching on) ==")
    eng = Engine(model, EngineConfig(kv_pool_bytes=8 << 20, chunk_size=16),
                 params=params)
    for i in range(3):
        eng.submit(Request(rid=f"req{i}", prompt=list(range(10 + 2 * i)),
                           sampling=SamplingParams(max_new_tokens=8)))
    for r in eng.run_until_done():
        print(f"  {r.rid}: out={r.output}")
    stats = eng.mgr.memory_stats()
    print(f"  pool: used={stats.used_units}u cached={stats.evictable_units}u "
          f"free={stats.free_units}u")


if __name__ == "__main__":
    main()
