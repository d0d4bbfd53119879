"""Multi-pod dry-run driver (deliverable e).

For one (arch x shape x mesh) cell: build the production mesh, lower +
compile the step with ShapeDtypeStruct inputs (no allocation), print
``memory_analysis`` / ``cost_analysis``, and parse per-device collective
bytes from the optimized HLO. Results go to JSON for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b \
      --shape decode_32k [--multi-pod] [--out dryrun_results/]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import os
import re
import sys
import time
import traceback


COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_stats(hlo_text: str):
    """Per-device collective bytes by op kind, parsed from optimized HLO.

    Convention: bytes = result-shape bytes of the op on one device (the
    received volume), summed over all collective instructions."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    alts = "|".join(k + "(?:-start)?" for k in COLLECTIVES)
    pat = re.compile(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (" + alts + r")\(")
    for line in hlo_text.splitlines():
        m = pat.match(line.strip())
        if not m:
            continue
        shape_tok, op = m.groups()
        k = op[:-6] if op.endswith("-start") else op
        total = sum(shape_bytes(t)
                    for t in re.findall(r"\w+\[[\d,]*\]", shape_tok))
        out[k]["count"] += 1
        out[k]["bytes"] += total
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import ARCHS, SHAPES_BY_NAME, shapes_for
    from ..launch.input_specs import (default_micro_batches, serve_cell,
                                      train_cell, wants_fsdp)
    from ..launch.mesh import production_dist
    from ..models.registry import build_model
    from ..models.params import param_struct
    from ..training import optimizer as opt

    cfg = ARCHS[arch]
    shape = SHAPES_BY_NAME[shape_name]
    if shape not in shapes_for(cfg):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "long_500k needs sub-quadratic attention; this "
                          "arch is pure full-attention (DESIGN.md)"}
    sp = shape.kind == "decode" and shape.global_batch < 32
    dist = production_dist(multi_pod=multi_pod, sp=sp)
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "devices": int(dist.dp * dist.tp) if not sp else
           int(dist.mesh.devices.size), "sp": sp}
    rec["devices"] = int(dist.mesh.devices.size)

    if shape.kind == "train":
        fsdp = wants_fsdp(cfg)
        import dataclasses as _dc
        dist = _dc.replace(dist, fsdp=fsdp)
        model = build_model(cfg, dist)
        rec["fsdp"] = fsdp
        # clamp so each microbatch still covers the DP width
        micro = min(default_micro_batches(cfg),
                    shape.global_batch // dist.dp)
        cell = train_cell(model, cfg, shape, dist, micro)
        rec["micro_batches"] = micro
        specs = model.specs()
        pstruct = model.struct()
        mesh = dist.mesh
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
        ostate = opt.OptState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pstruct),
            nu=jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pstruct))
        oshard = opt.OptState(
            step=NamedSharding(mesh, P()),
            mu=opt.zero1_shardings(specs, pstruct, mesh),
            nu=opt.zero1_shardings(specs, pstruct, mesh))
        acfg = opt.AdamWConfig()
        kwargs = cell.kwargs

        def train_step(params, state, tokens, targets):
            b = tokens.shape[0]
            mb = b // micro

            def split(a, name=""):
                if name == "mrope_pos":   # (3, B, T): batch is dim 1
                    r = a.reshape(a.shape[0], micro, mb, *a.shape[2:])
                    return jnp.moveaxis(r, 1, 0)
                return a.reshape(micro, mb, *a.shape[1:])

            kw_split = {k: split(v, k) for k, v in kwargs.items()}

            def mstep(carry, xs):
                gsum, lsum = carry
                tok, tgt, kws = xs
                loss, grads = jax.value_and_grad(
                    lambda p: model.train_loss(p, tok, tgt, **kws))(params)
                return (jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads),
                    lsum + loss), None

            gz = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (gsum, lsum), _ = jax.lax.scan(
                mstep, (gz, jnp.float32(0)),
                (split(tokens), split(targets), kw_split))
            grads = jax.tree.map(lambda g: g / micro, gsum)
            params2, state2, _ = opt.update(acfg, params, grads, state)
            return lsum / micro, params2, state2

        # NOTE: extras (enc/mm embeds) passed positionally (pjit forbids
        # kwargs when in_shardings is given)
        if kwargs:
            kw_names = sorted(kwargs)
            kw_structs = [kwargs[k] for k in kw_names]

            def train_step_kw(params, state, tokens, targets, *kw_vals):
                nonlocal kwargs
                kwargs = dict(zip(kw_names, kw_vals))
                return train_step(params, state, tokens, targets)

            jitted = jax.jit(
                train_step_kw,
                in_shardings=(pshard, oshard, None, None)
                + (None,) * len(kw_structs),
                donate_argnums=(0, 1))
            lowered = jitted.lower(pstruct, ostate, *cell.args, *kw_structs)
        else:
            jitted = jax.jit(
                train_step,
                in_shardings=(pshard, oshard, None, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(pstruct, ostate, *cell.args)
    else:
        from ..models import attention as _A
        _A.set_write_mode("dus")   # 0-copy buffer writes (see EXPERIMENTS.md)
        model = build_model(cfg, dist)
        model.param_dtype = jnp.bfloat16   # serving weights are bf16
        cell = serve_cell(cfg=cfg, model=model, shape=shape, dist=dist)
        rec["buffer_units_per_device"] = cell.buffer_units
        rec.update(cell.notes)
        pstruct = model.struct()

        def serve(params, buffer, batch):
            return model.serve_step(params, buffer, batch,
                                    prefill=cell.kwargs["prefill"])

        jitted = jax.jit(serve, donate_argnums=(1,))
        lowered = jitted.lower(pstruct, *cell.args)

    rec["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                rec[k] = int(v)
        args_b = rec.get("argument_size_in_bytes", 0)
        alias_b = rec.get("alias_size_in_bytes", 0)
        temp_b = rec.get("temp_size_in_bytes", 0)
        out_b = rec.get("output_size_in_bytes", 0)
        rec["peak_bytes_per_device"] = args_b + temp_b + (out_b - alias_b)
    cost = compiled.cost_analysis()
    if cost:
        rec["flops_per_device"] = float(cost.get("flops", 0.0))
        rec["bytes_accessed_per_device"] = float(
            cost.get("bytes accessed", 0.0))
    rec["collectives"] = collective_stats(compiled.as_text())
    rec["status"] = "ok"
    return rec


def main():
    # process setup for the dry run only, never at import: 512 host
    # devices stand in for the production mesh (read when the backend
    # starts), and KV pools exceed 2^31 units
    os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_XLA_EXTRA", "") +
                               " --xla_force_host_platform_device_count=512")
    import jax
    jax.config.update("jax_enable_x64", True)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)

    def one(arch, shape_name, multi_pod):
        tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag} (cached)")
            return
        print(f"[run ] {tag}", flush=True)
        try:
            rec = run_cell(arch, shape_name, multi_pod)
        except Exception as e:
            rec = {"arch": arch, "shape": shape_name, "status": "error",
                   "error": repr(e),
                   "trace": traceback.format_exc()[-4000:]}
        with open(path, "w") as fh:
            json.dump(rec, fh, indent=1)
        print(f"[done] {tag}: {rec.get('status')} "
              f"(compile {rec.get('compile_s', '-')}s)", flush=True)

    if args.all:
        from ..configs import ALL_SHAPES, ARCHS
        for arch in sorted(ARCHS):
            for shape in ALL_SHAPES:
                one(arch, shape.name, args.multi_pod)
    else:
        one(args.arch, args.shape, args.multi_pod)


if __name__ == "__main__":
    main()
