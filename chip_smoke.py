"""Chip smoke run: serve granite-3-2b at its published widths on one TPU.

Builds the model with random weights from ``--seed``, checks the compiled
in-place paged attention kernel against its jnp oracle, and serves a small
seeded request mix through ``Engine`` three times in one process: the
gather path (jnp reference attention, synchronous loop, 512-token steps),
the same path with 256-token steps, and ``attention_impl="kernel"`` (the
Pallas paged kernel reading the KV pool in place). It checks that every request finished with finite logits, that
both other legs agree with the default one on greedy tokens (fork-aware,
see ``repro.analysis.greedy_equiv``, with the fixed limits below), that
the kernel leg's compiled serve step holds a ``tpu_custom_call`` (no
interpreter), and that the KV buffer takes its nominal bytes on the
device.

    python3 chip_smoke.py                  # one TPU chip
    python3 chip_smoke.py --cpu-rehearsal  # same flow, reduced() on the CPU

The numbers printed on the way are a smoke run's log, not benchmark
metrics. The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``, printed only when every phase passed on
a TPU. Without a TPU, and in the rehearsal, the script exits non-zero and
prints no verdict.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "granite-3-2b"
POOL_BYTES = 2 << 30          # 2 GiB of KV cache: ~26 K granite tokens
SHARED_PREFIX = 512
MAX_NEW_TOKENS = 32
# prompt lengths; "shared" prompts start with the same SHARED_PREFIX tokens
PROMPTS = (("r0", 64, False), ("r1", 200, False), ("r2", 448, False),
           ("r3", 608, True), ("r4", 1000, False), ("r5", 1536, False),
           ("r6", 2048, False))
LATE = ("r7", 672, True)      # arrives once r3's prefix is cached
# (leg, attention_impl, token budget per step): the gather path, the same
# reference attention under another schedule (measures its own numerical
# noise at this width), and the in-place Pallas kernel
LEGS = (("ref", "ref", 512), ("ref-256", "ref", 256),
        ("kernel", "kernel", 512))
# Agreement limits between two legs, fixed (PERF.md, Findings). At 40
# layers x d_model 2048 two sound legs differ by up to ~0.24 in a logit
# row on the same inputs (bf16 activations; logit std ~0.9), and legs
# with a planted attention-mask or KV-write fault by 2.5 or more: NOISE_MAX
# is the discriminating limit. A fork's gap is at most twice that noise,
# so FORK_TOL is a backstop: sound forks reach ~0.13, and each faulty run
# has forks past 0.25 in 3 to 7 of its 8 requests. TIE_FORK_TOL (0.025)
# was measured at reduced() widths, where the noise is an order smaller.
FORK_TOL = 0.25     # both legs' rows must score both picks within this
NOISE_MAX = 0.5     # largest |logit diff| of two legs on the same inputs
NOISE_RATIO = 2.0   # ref-vs-kernel noise over ref-vs-ref-256 noise
ORACLE_TOL = 2 ** -7   # kernel vs oracle, relative to the largest |output|


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def make_prompts(rng, vocab, scale):
    """Seeded prompts: {rid: tokens}; shared ones share one prefix."""
    prefix = rng.integers(0, vocab, SHARED_PREFIX // scale).tolist()
    out = {}
    for rid, n, shared in PROMPTS + (LATE,):
        n //= scale
        if shared:
            tail = rng.integers(0, vocab, n - len(prefix)).tolist()
            out[rid] = prefix + tail
        else:
            out[rid] = rng.integers(0, vocab, n).tolist()
    return out


def run_leg(model, cfg, prompts, params, seed, scale):
    """Serve the mix through one Engine; returns (engine, last prepared
    step, wall seconds of engine build + serving)."""
    from repro.serving import Engine, Request, SamplingParams

    t0 = time.perf_counter()
    eng = Engine(model, cfg, params=params, seed=seed)
    last = []
    dispatch = eng.runner.dispatch

    def recording_dispatch(p, prep):
        last[:] = [prep]
        return dispatch(p, prep)

    eng.runner.dispatch = recording_dispatch

    def req(rid):
        return Request(rid=rid, prompt=prompts[rid], sampling=SamplingParams(
            max_new_tokens=MAX_NEW_TOKENS))

    first = {rid: req(rid) for rid, _, _ in PROMPTS}
    for r in first.values():
        eng.submit(r)
    sharer = first["r3"]
    while sharer.seq is None or \
            sharer.seq.num_computed < SHARED_PREFIX // scale:
        check(eng.step_count < 1000, "shared prefix never computed")
        eng.step()
    eng.submit(req(LATE[0]))
    eng.run_until_done()
    return eng, last[0], time.perf_counter() - t0


def check_leg(eng, label, n_req):
    import numpy as np

    done = {r.rid: r for r in eng.finished}
    check(len(done) == n_req, f"{label}: {len(done)}/{n_req} finished")
    for rid, r in done.items():
        check(len(r.output) == MAX_NEW_TOKENS,
              f"{label}: {rid} produced {len(r.output)} tokens")
        rows = eng.sample_log[rid]
        check(len(rows) == MAX_NEW_TOKENS and all(
            np.isfinite(row).all() for row in rows),
            f"{label}: {rid} has missing or non-finite logits")


def print_agreement(ref, other):
    """Per request: tokens equal before the first divergence, the largest
    |logit| difference between the two legs' rows that were computed from
    the same tokens (those before the fork and the fork row itself), and
    the gaps at the fork. Returns the largest difference over all
    requests: the numerical noise between the two legs."""
    import numpy as np

    outs = {r.rid: r.output for r in other.finished}
    worst = 0.0
    for r in sorted(ref.finished, key=lambda r: r.rid):
        a, b = r.output, outs[r.rid]
        n = next((j for j in range(len(a)) if a[j] != b[j]), len(a))
        rows = zip(ref.sample_log[r.rid][:n + 1],
                   other.sample_log[r.rid][:n + 1])
        diff = max(float(np.abs(x - y).max()) for x, y in rows)
        worst = max(worst, diff)
        fork = ""
        if n < len(a):
            la, lb = ref.sample_log[r.rid][n], other.sample_log[r.rid][n]
            fork = (f"; fork gaps {float(la.max() - la[b[n]]):.4e} / "
                    f"{float(lb.max() - lb[a[n]]):.4e}")
        print(f"  {r.rid}: {n}/{len(a)} tokens equal before any fork; "
              f"max |logit diff| on same inputs {diff:.4e}{fork}")
    return worst


def kernel_vs_oracle(t, s, interpret):
    """Largest |error| of the in-place paged kernel against its f32 jnp
    oracle, relative to the oracle's largest |output|, on one seeded packed
    step at granite's 8 KV heads x 4 query groups x head_dim 64 and the
    serve path's bf16: four segments of t/4 query tokens over s/4 slots of
    shuffled pool pages, whose chunks start at the segment's first
    position (a fresh prompt), at its end, and between."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.kernel import (paged_attention,
                                                      plan_work)
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.models.blocks_attn import paged_pages_per_wave, paged_q_block

    kvl, g, d, tpp, nl = 8, 4, 64, 16, 2
    rng = np.random.default_rng(0)
    seg_t, seg_s = t // 4, s // 4
    npg = seg_s // tpp
    vp = 4 * npg + 3
    rows = jnp.asarray(rng.standard_normal(
        (vp * nl, 2 * tpp * kvl * d // 128, 128)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((t, kvl, g, d)), jnp.bfloat16)
    start = np.arange(4) * (seg_s - seg_t) // 3
    seg = np.repeat(np.arange(4), seg_t).astype(np.int32)
    pos = (start[:, None] + np.arange(seg_t)).reshape(-1).astype(np.int32)
    ids = jnp.asarray(rng.permutation(vp)[:4 * npg].astype(np.int32))
    runs = (np.arange(4, dtype=np.int32) * npg, np.full(4, npg, np.int32))
    meta = tuple(map(jnp.asarray, (seg, pos) + runs))
    qb = paged_q_block(kvl, g)
    work = plan_work(*meta, q_block=qb, tokens_per_page=tpp)
    out = paged_attention(
        q, rows, ids, work, 1, num_layers=nl, tokens_per_page=tpp,
        q_block=qb, interpret=interpret,
        pages_per_wave=paged_pages_per_wave((vp, nl, 2, tpp, kvl, d)))
    with jax.default_matmul_precision("highest"):
        want = paged_attention_ref(
            q.astype(jnp.float32), rows.astype(jnp.float32), ids, *meta, 1,
            num_layers=nl, tokens_per_page=tpp)
    err = jnp.abs(out.astype(jnp.float32) - want).max()
    return float(err / jnp.abs(want).max())


def param_bytes(params):
    """{dtype name: bytes} of a param tree."""
    import jax

    out = {}
    for a in jax.tree.leaves(params):
        out[a.dtype.name] = out.get(a.dtype.name, 0) + a.nbytes
    return out


def buffer_device_bytes(buf):
    """Bytes the compiled allocation of ``buf``'s shape takes on its
    device (layout padding included)."""
    import jax
    import jax.numpy as jnp

    alloc = jax.jit(lambda: jnp.zeros(buf.shape, buf.dtype),
                    out_shardings=buf.sharding)
    return alloc.lower().compile().memory_analysis().output_size_in_bytes


def serve_step_hlo(eng, prep):
    """Compiled HLO of the serve step ``eng`` dispatched for ``prep``."""
    r = eng.runner
    fn = r._steps[prep.info["key"] + (r.attention_impl,)]
    batch = r._to_batch(prep.arrs)
    return fn.lower(eng.params, r.buffer, batch).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the same flow at reduced() size on the CPU "
                         "(exits 3, never reports ok)")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"jax {jax.__version__}  platform={dev.platform}  "
          f"device_kind={dev.device_kind!r}  devices={len(devs)}", flush=True)
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print("chip_smoke: no TPU found; this check needs one chip",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import numpy as np
    from jax import monitoring

    from repro.analysis.greedy_equiv import assert_greedy_equiv
    from repro.configs import ARCHS, reduced
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.registry import build_model
    from repro.models.tp import single_device_dist
    from repro.serving import EngineConfig

    print(f"compile cache: {use_compile_cache()}")
    compile_s = [0.0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    monitoring.register_event_duration_secs_listener(on_duration)

    cfg = ARCHS[ARCH]
    scale, pool = 1, POOL_BYTES
    if args.cpu_rehearsal:
        cfg, scale, pool = reduced(cfg), 8, 64 << 20
    model = build_model(cfg, single_device_dist())
    prompts = make_prompts(np.random.default_rng(args.seed), cfg.vocab_size,
                           scale)
    n_req = len(prompts)
    print(f"smoke run (not a benchmark): {cfg.name}, {cfg.num_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size}; {n_req} requests, "
          f"prompts {min(map(len, prompts.values()))}-"
          f"{max(map(len, prompts.values()))} tokens, "
          f"{MAX_NEW_TOKENS} new tokens each; pool {pool / 2**30:.2f} GiB")

    err = kernel_vs_oracle(256, 2048, interpret=args.cpu_rehearsal)
    print(f"paged kernel vs f32 jnp oracle (bf16, 4 segments, t=256, "
          f"s=2048): max |error| / max |output| {err:.4e} "
          f"(limit {ORACLE_TOL:.4e})")
    check(err <= ORACLE_TOL, "paged kernel disagrees with its oracle")

    engines = {}
    params = None
    for leg, impl, budget in LEGS:
        ecfg = EngineConfig(kv_pool_bytes=pool, max_running=n_req,
                            chunk_size=budget // scale,
                            max_num_batched_tokens=budget // scale,
                            attention_impl=impl, record_sample_logits=True,
                            seed=args.seed)
        c0 = compile_s[0]
        n0 = len(getattr(model, "_serve_jit_cache", ()))
        eng, prep, wall = run_leg(model, ecfg, prompts, params, args.seed,
                                  scale)
        params = eng.params        # later legs serve the same weights
        engines[leg] = eng
        check_leg(eng, leg, n_req)
        units = eng.mgr.geometry.total_units
        used = max(m.used_units for m in eng.metrics)
        print(f"[{leg}] finished {len(eng.finished)}/{n_req} in "
              f"{eng.step_count} steps; engine build + serve {wall:.3f} s "
              f"wall, of which backend compile {compile_s[0] - c0:.3f} s; "
              f"new serve-step shapes {len(eng.runner._steps) - n0}; "
              f"prefix-hit tokens "
              f"{eng.mgr.prefix_hit_tokens_total}; pool units used "
              f"{used}/{units}; param bytes {param_bytes(eng.params)}",
              flush=True)
        check(eng.mgr.prefix_hit_tokens_total >= SHARED_PREFIX // scale,
              f"{leg}: shared prefix was not served from cache")
        if leg == "kernel":
            has_kernel = "tpu_custom_call" in serve_step_hlo(eng, prep)
            print(f"[kernel] compiled serve step holds tpu_custom_call: "
                  f"{has_kernel}")
            check(has_kernel or args.cpu_rehearsal,
                  "kernel leg's serve step has no tpu_custom_call")

    print("ref vs ref-256 (same attention, 256-token steps):")
    noise = print_agreement(engines["ref"], engines["ref-256"])
    print("ref vs kernel:")
    k_noise = print_agreement(engines["ref"], engines["kernel"])
    print(f"logit noise on same inputs: ref vs ref-256 {noise:.4e}, ref vs "
          f"kernel {k_noise:.4e} (limits {NOISE_MAX} each, kernel at most "
          f"{NOISE_RATIO}x ref-256)")
    check(noise <= NOISE_MAX and k_noise <= NOISE_MAX,
          "legs differ by more than numerical noise on the same inputs")
    check(k_noise <= NOISE_RATIO * noise,
          "kernel noise exceeds the reference's own schedule noise")
    for leg in ("ref-256", "kernel"):
        forked = assert_greedy_equiv(engines["ref"], engines[leg],
                                     f"ref vs {leg}", tol=FORK_TOL)
        print(f"greedy tokens ref == {leg}: {n_req - len(forked)} requests "
              f"identical, {len(forked)} forked at a near-tie (fork "
              f"tolerance {FORK_TOL})")

    buf = engines["kernel"].runner.buffer
    on_dev = buffer_device_bytes(buf)
    print(f"kv buffer: shape {tuple(buf.shape)} {buf.dtype}, nominal "
          f"{buf.nbytes} bytes, on device {on_dev} bytes")
    check(on_dev == buf.nbytes, "kv buffer is padded on the device")
    stats = dev.memory_stats() or {}
    print(f"device memory: peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}, bytes_limit "
          f"{stats.get('bytes_limit', 'not reported')}")

    if args.cpu_rehearsal:
        print("cpu rehearsal passed; not a chip run (exit 3)")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
