"""Shared test helpers: one reduced model (+ params) per arch for the whole
session. Engines are recreated freely across tests and A/B legs; sharing
the model instance also shares its serve-step jit cache (see
ModelRunner), which is most of the suite's wall-clock.

REPRO_ATTENTION_IMPL=kernel flips the default attention implementation so
the same suite exercises the Pallas varlen kernel path (the tier-1 CI
kernel leg); tests that pass attention_impl explicitly are unaffected.

``assert_greedy_equiv`` (from ``repro.analysis.greedy_equiv``) is the
shared fork-aware cross-layout greedy comparison."""
import os

from repro.analysis.greedy_equiv import assert_greedy_equiv  # noqa: F401
from repro.configs import ARCHS, reduced
from repro.models.registry import build_model
from repro.models.tp import single_device_dist
from repro.serving import Engine, EngineConfig

_MODELS = {}    # arch -> (model, cfg, params)
# "<arch>+wide": reduced() at kv_heads * head_dim = 256, so one token's K
# (or V) spans two 128-lane rows of the KV buffer, as at published widths
WIDE_KV = dict(num_heads=8, num_kv_heads=4, head_dim=64)


def get_model(arch):
    if arch not in _MODELS:
        name, _, variant = arch.partition("+")
        cfg = reduced(ARCHS[name], **(WIDE_KV if variant == "wide" else {}))
        model = build_model(cfg, single_device_dist())
        _MODELS[arch] = (model, cfg, model.init(0))
    return _MODELS[arch]


def make_engine(arch="granite-3-2b", **cfg_kw):
    model, cfg, params = get_model(arch)
    kw = dict(kv_pool_bytes=8 << 20, max_running=4, chunk_size=8,
              attention_impl=os.environ.get("REPRO_ATTENTION_IMPL", "ref"))
    kw.update(cfg_kw)
    return Engine(model, EngineConfig(**kw), params=params), cfg
