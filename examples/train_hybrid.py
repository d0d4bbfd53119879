"""Train the reduced Zamba2-style hybrid (Mamba2 + shared attention) with
fault-tolerant checkpointing; kill-and-resume is exact.
Run: PYTHONPATH=src python examples/train_hybrid.py"""
import numpy as np

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.training import AdamWConfig, SyntheticLM, Trainer, TrainerConfig


def main():
    use_compile_cache()
    cfg = reduced(ARCHS["zamba2-1.2b"])
    model = build_model(cfg, single_device_dist())
    trainer = Trainer(model, AdamWConfig(lr=5e-3, warmup_steps=10,
                                         total_steps=300),
                      TrainerConfig(ckpt_dir="/tmp/hybrid_ckpt",
                                    ckpt_every=50, micro_batches=2))
    params, state = trainer.init_state(0)
    data = SyntheticLM(cfg.vocab_size, seq_len=64, global_batch=8)
    params, state, hist = trainer.run(
        params, state, data, num_steps=120, log_every=20,
        on_metrics=lambda s, m: print(
            f"step {s}: loss={m['loss']:.3f} gnorm={m['grad_norm']:.2f} "
            f"{m['sec_per_step']*1e3:.0f}ms"))
    print(f"loss: {hist[0]:.3f} -> {np.mean(hist[-10:]):.3f}")
    last = trainer.ckpt.latest_step()
    p2, s2, meta = trainer.restore(last)
    print(f"restored step {last} (model={meta['extra']['model']}) — resume OK")


if __name__ == "__main__":
    main()
