"""Speculative decoding (§6.1): draft + target share ONE Jenga pool with two
page sizes. Run: PYTHONPATH=src python examples/spec_decode_demo.py"""
from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving.spec_decode import SpecDecodeConfig, SpecDecodeEngine


def main():
    use_compile_cache()
    tcfg = reduced(ARCHS["granite-3-2b"])
    dcfg = reduced(ARCHS["internlm2-1.8b"], num_layers=2,
                   vocab_size=tcfg.vocab_size)
    dist = single_device_dist()
    sd = SpecDecodeEngine(build_model(tcfg, dist), build_model(dcfg, dist),
                          SpecDecodeConfig(k=3, kv_pool_bytes=16 << 20))
    sizes = {s.name: s.page_units for s in sd.mgr.specs}
    print("pool page sizes:", sizes,
          "LCM large page:", sd.mgr.geometry.large_page_units)
    out = sd.generate(list(range(16)), max_new_tokens=12)
    print("output:", out)
    print("accepted per round:", sd.accept_lengths)


if __name__ == "__main__":
    main()
