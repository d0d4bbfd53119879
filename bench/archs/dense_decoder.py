"""A dense decoder: Llama-style pre-norm blocks, each with rotary
grouped-query attention and a SwiGLU MLP (internlm2-1.8b, granite-3-2b).

The weights are ``bench/weights.py``'s, the plain reference and its fp8
control ``bench/reference.py``'s, the FLOP count ``bench/flops.py``'s;
``bench/spec.py`` says what an architecture module provides.
"""
from __future__ import annotations

from bench import reference, weights
from bench.flops import FlopModel

make = weights.make
program_tree = weights.program_tree
gaps = reference.gaps


def flop_model(cfg: dict) -> FlopModel:
    return FlopModel.from_config(cfg)
