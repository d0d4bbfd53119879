"""Serve three heterogeneous families through the SAME engine — one memory
manager for SWA mixes, hybrid SSM state, and cross-attention caches; Jenga
vs PagedAttention-baseline peak pool usage.
Run: PYTHONPATH=src python examples/serve_heterogeneous.py"""
from repro.configs import ARCHS, reduced
from repro.core.request import MMItem
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving import Engine, EngineConfig, Request, SamplingParams


def serve(arch: str, mode: str):
    cfg = reduced(ARCHS[arch])
    model = build_model(cfg, single_device_dist())
    eng = Engine(model, EngineConfig(kv_pool_bytes=4 << 20, chunk_size=16,
                                     memory_mode=mode))
    kw = {}
    if cfg.family == "encdec":
        kw["encoder_items"] = (MMItem(0, cfg.encoder_seq, mm_hash=5),)
    for i in range(3):
        eng.submit(Request(rid=f"r{i}", prompt=list(range(40)),
                           sampling=SamplingParams(max_new_tokens=4), **kw))
    eng.run_until_done(max_steps=600)
    return max(m.used_units for m in eng.metrics)


def main():
    use_compile_cache()
    for arch in ("h2o-danube-3-4b", "zamba2-1.2b", "whisper-tiny"):
        j = serve(arch, "jenga")
        p = serve(arch, "paged-baseline")
        print(f"{arch:20s} peak used units: jenga={j:>9} paged={p:>9} "
              f"({p/max(1,j):.2f}x waste)")


if __name__ == "__main__":
    main()
