"""Fork-aware comparison of two engines' greedy outputs (see the TIE_EPS
note in ``repro.serving.engine``): the check the tests and
``chip_smoke.py`` apply across layouts and attention implementations."""
from __future__ import annotations

# Fork tolerance for cross-layout greedy comparisons: at a token
# divergence, BOTH modes' recorded fp32 logit rows must place BOTH chosen
# tokens within this gap of the row max — i.e. the decision was genuinely
# ambiguous under bf16 reduction-order noise (measured <= ~4e-3 on the
# reduced() configs; real masking/leak bugs shift logits by >> 1e-1).
# Wider than TIE_EPS on purpose: the band makes near-ties deterministic
# per mode, the fork check bounds what may differ across modes. At
# published widths the noise is an order larger (granite-3-2b, 40 layers
# x d_model 2048: two schedules of the same reference attention fork at
# gaps up to ~0.06), so chip_smoke.py passes its own fixed, wider limit.
TIE_FORK_TOL = 2.5e-2


def assert_greedy_equiv(ref_eng, other_eng, label="", tol=TIE_FORK_TOL):
    """Greedy outputs of two drained engines must be token-identical up
    to genuinely ambiguous forks. Exact equality is required until the
    first differing token of each request; that decision must be a
    near-tie in BOTH engines' recorded logit rows (within ``tol``), after
    which the trajectories have legitimately forked and later tokens are
    incomparable. Requires ``record_sample_logits=True`` on both engines.
    Raises AssertionError otherwise; returns the set of forked request ids
    (empty == bitwise-exact)."""
    ref = {r.rid: list(r.output) for r in ref_eng.finished}
    other = {r.rid: list(r.output) for r in other_eng.finished}
    if set(ref) != set(other):
        raise AssertionError((label, set(ref) ^ set(other)))
    forked = set()
    for rid in ref:
        a, b = ref[rid], other[rid]
        n = min(len(a), len(b))
        i = next((j for j in range(n) if a[j] != b[j]), None)
        if i is None:
            # identical prefix implies identical EOS decisions
            if len(a) != len(b):
                raise AssertionError((label, rid, a, b))
            continue
        la = ref_eng.sample_log[rid][i]
        lb = other_eng.sample_log[rid][i]
        ga = float(la.max() - la[b[i]])   # other's pick, scored by ref
        gb = float(lb.max() - lb[a[i]])   # ref's pick, scored by other
        if ga > tol or gb > tol:
            raise AssertionError((
                label, rid, i, a[i], b[i], ga, gb,
                "divergence beyond tie tolerance — not reduction-order "
                "noise"))
        forked.add(rid)
    return forked
