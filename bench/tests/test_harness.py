"""The harness end to end at a tiny size on the CPU.

Each test builds a benchmark root of its own in a temporary directory: a
``BENCHMARK.json``, a configuration, a traffic mix and a per-layer metric
that exist only as new files there. The run skips the harness's look for a
chip and uses a virtual clock (1 ms per reading, sleeps advance it), so it
is deterministic and quick. Faults are planted under the timed path to see
``correct`` come out false.
"""
import json
import sys
from pathlib import Path

import pytest

from bench import harness, reference, weights
from bench.spec import Bench

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {
    "name": "tiny-dense", "family": "dense", "num_layers": 2, "d_model": 64,
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab_size": 512, "rope_theta": 10000.0, "norm_eps": 1e-05,
    "tie_embeddings": True, "tokens_per_page": 4,
}
TINY_CHAT = {
    "arrival": {"process": "poisson", "rate_per_s": 4.0},
    "sessions": {"system_prompts": 2, "system_prompt_tokens": 24,
                 "zipf_s": 1.1, "turns_min": 1, "turns_max": 3,
                 "think_s_mean": 0.2},
    "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
    "output_tokens": {"median": 6, "sigma": 0.5, "min": 3, "max": 10},
    "max_context": 200,
    "warmup": {"seconds": 0.5},
}
TINY_BACKLOG = {
    "arrival": {"process": "backlog"},
    "backlog_requests": 300,
    "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
    "output_tokens": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
    "max_context": 200,
    "warmup": {"seconds": 0.05},
}
# a reading of the window that only this new file defines
NEW_METRIC = '''
def read(run):
    return float(len(run.steps)) if run.steps else None
'''
# readers of bench/readings.py, as a new cell's metric files would be
READER = "from bench.readings import {fn} as read  # noqa: F401\n"
# sound tiny runs read gaps of about 1e-3 (bf16 activations against the
# fp32 reference); a token altered where it is sampled reads 0.1 or more
TINY_LIMIT = 0.02


def make_root(tmp: Path) -> Path:
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    (tmp / "bench" / "metrics").mkdir()
    cfg = {"model": TINY_MODEL,
           "engine": {"kv_pool_bytes": 4 << 20, "max_running": 16,
                      "max_num_batched_tokens": 64, "chunk_size": 64},
           "check": {"max_logit_gap": TINY_LIMIT}}
    (tmp / "bench/configs/tiny-dense.json").write_text(json.dumps(cfg))
    (tmp / "bench/traffic/tiny_chat.json").write_text(json.dumps(TINY_CHAT))
    (tmp / "bench/traffic/tiny_backlog.json").write_text(
        json.dumps(TINY_BACKLOG))
    for name, fn in (("prefix_hit_frac", "prefix_hit_frac"),
                     ("queue_wait_p95_ms", "queue_wait_p95_ms"),
                     ("decode_batch_mean", "decode_batch_mean"),
                     ("mfu.batch", "mfu")):
        (tmp / "bench/metrics" / f"{name}.py").write_text(READER.format(fn=fn))
    (tmp / "bench/metrics/steps_in_window.py").write_text(NEW_METRIC)
    cell, batch = "tiny-dense.tiny_chat", "tiny-dense.tiny_backlog"
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny-dense", "source": "test",
                     "file": "bench/configs/tiny-dense.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": "tiny-dense",
                       "traffic": "tiny_chat", "chips": 1, "why": "test"},
                      {"name": batch, "config": "tiny-dense",
                       "traffic": "tiny_backlog", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": [cell]},
            {"name": "output_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": [batch]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "prefix_hit_frac", "unit": "%", "better": "higher",
             "source": "program_counter", "layer": "cache manager",
             "moves": "ttft_p90_ms", "workloads": [cell]},
            {"name": "queue_wait_p95_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "scheduler",
             "moves": "ttft_p90_ms"},
            {"name": "steps_in_window", "unit": "steps", "better": "higher",
             "source": "host_clock", "layer": "engine loop",
             "moves": "ttft_p90_ms", "workloads": [cell]},
            {"name": "decode_batch_mean", "unit": "requests",
             "better": "higher", "source": "program_counter",
             "layer": "scheduler", "moves": "output_tokens_per_s",
             "workloads": [batch]},
            {"name": "mfu.batch", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "model step",
             "moves": "output_tokens_per_s", "workloads": [batch]}],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


class VirtualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 0.0)


def run(root, seed=2 ** 31 + 7, trace=False, control=False,
        cell="tiny-dense.tiny_chat"):
    vc = VirtualClock()
    return harness.run_cell(root, cell, seed, 1.0, trace,
                            0.0, require_chip=False, clock=vc,
                            sleep=vc.sleep, control=control)


def window_line(out):
    return next(line for line in out.splitlines()
                if line.startswith("window:"))


@pytest.fixture
def root(tmp_path, monkeypatch):
    # entries compiled for this CPU would only warn when read back
    monkeypatch.setattr(harness, "setup_compile_cache", lambda root: "off")
    return make_root(tmp_path)


def test_new_files_alone_define_a_cell(root):
    b = Bench(root)
    assert b.cell("tiny-dense.tiny_chat")["traffic"] == "tiny_chat"
    assert b.config("tiny-dense")["model"]["d_model"] == 64
    assert b.mix("tiny_chat")["arrival"]["rate_per_s"] == 4.0
    assert sorted(b.readers("tiny-dense.tiny_chat")) == [
        "prefix_hit_frac", "queue_wait_p95_ms", "steps_in_window"]
    assert [m["name"] for m in b.end_to_end("tiny-dense.tiny_chat")] == [
        "ttft_p90_ms", "setup_s"]


def test_sound_run_is_correct_and_reports_its_metrics(root, capsys):
    res = run(root)
    window = window_line(capsys.readouterr().out)
    # the rehearsal compiled every shape the window uses
    assert "compile 0 (" in window and "cache_load 0 (" in window
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "check"
    gap = res["check"]["logit_gap"]
    assert gap["value"] <= gap["limit"] == TINY_LIMIT

    traced = run(root, trace=True)
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("logit_gap:") and "limit" in err[-2]
    assert traced["correct"] is True
    assert {"prefix_hit_frac", "queue_wait_p95_ms",
            "steps_in_window"} <= set(traced["metrics"])
    assert traced["metrics"]["prefix_hit_frac"]["value"] > 0


def test_same_seed_same_inputs(root):
    a, b = run(root, seed=11), run(root, seed=11)
    assert a["attempted"] == b["attempted"]
    assert a["check"] == b["check"]


def test_token_altered_where_it_is_produced_fails(root, monkeypatch):
    from repro.serving.engine import Engine
    sample = Engine._sample

    def altered(self, req, logits):
        return (sample(self, req, logits) + 1) % TINY_MODEL["vocab_size"]

    monkeypatch.setattr(Engine, "_sample", altered)
    res = run(root)
    assert res["correct"] is False
    assert res["check"]["logit_gap"]["value"] > TINY_LIMIT


def test_step_that_leaves_the_kv_cache_unchanged_fails(root, monkeypatch):
    from repro.models import blocks_attn
    monkeypatch.setattr(blocks_attn, "attn_write",
                        lambda buf, *args, **kw: buf)
    res = run(root)
    assert res["correct"] is False


def test_fp8_control_fails_the_limit(root):
    """The control, the reference in fp8 e4m3, its tokens put in the place
    of the served ones on the same prompts: the run's own verdict comes out
    not correct, well past the sound run's gap."""
    sound = run(root)
    res = run(root, control=True)
    assert sound["correct"] is True and res["correct"] is False
    ctrl = res["check"]["logit_gap"]["value"]
    assert ctrl > TINY_LIMIT >= sound["check"]["logit_gap"]["value"]
    assert ctrl >= 3 * sound["check"]["logit_gap"]["value"]
    assert res["check"]["tokens"] == sound["check"]["tokens"]


def test_backlog_run_is_correct_and_reports_its_metrics(root, capsys):
    cell = "tiny-dense.tiny_backlog"
    res = run(root, cell=cell)
    window = window_line(capsys.readouterr().out)
    assert "compile 0 (" in window and "cache_load 0 (" in window
    assert res["correct"] is True
    assert set(res["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    # a trace on the CPU holds no device: the device share reads nothing,
    # and is left out rather than read as 0
    traced = run(root, cell=cell, trace=True)
    assert traced["correct"] is True
    assert set(traced["metrics"]) == {"decode_batch_mean"}


@pytest.mark.parametrize("fault", ["token_altered", "kv_unwritten"])
def test_backlog_faults_fail(root, monkeypatch, fault):
    """The faults a served cell can have, planted under the timed path of
    the backlog cell, the kind that ``BENCHMARK.json`` holds."""
    if fault == "token_altered":
        from repro.serving.engine import Engine
        sample = Engine._sample
        monkeypatch.setattr(
            Engine, "_sample", lambda self, req, logits:
            (sample(self, req, logits) + 1) % TINY_MODEL["vocab_size"])
    else:
        from repro.models import blocks_attn
        monkeypatch.setattr(blocks_attn, "attn_write",
                            lambda buf, *args, **kw: buf)
    res = run(root, cell="tiny-dense.tiny_backlog")
    assert res["correct"] is False
    assert res["check"]["logit_gap"]["value"] > TINY_LIMIT


def test_reference_agrees_with_itself_across_buckets():
    """Padding a sequence to a longer bucket changes no logit."""
    m = TINY_MODEL
    w = weights.make(m, 3)
    toks = list(range(1, 40))
    a = reference.logits(w, m, toks, [5, 38])
    b = reference.logits(w, m, toks + [7] * 600, [5, 38])
    assert abs(a - b).max() < 1e-4


def test_run_exits_nonzero_without_a_chip(capsys):
    sys.path.insert(0, str(BENCH.parent))
    from bench import run as run_mod
    rc = run_mod.main(["--workload", "internlm2-1.8b.reasoning_batch", "--seed",
                       "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr().out
    assert rc != 0
    assert "platform=cpu" in out and '"correct"' not in out
