"""The four-replica path of ``qwen3-4b-x4.sessions`` on the CPU's four
virtual devices, at a small size and past the harness's look for a chip:
every replica sits on a device of its own and is warmed up before the
window, the DDS router places the window's requests over them, and what
they served is ``correct``; with the decode step broken underneath on
every replica (a token altered, the cache left unchanged) it is not.

The run is a child process: the number of virtual devices is fixed when
JAX starts, and this process has started it with one."""
import json
import os
import subprocess
import sys

import pytest

import numpy as np

from bench import correctness, harness

CELL = "qwen3-4b-x4.sessions"

CHILD = r"""
import json, sys, time
import jax
from bench import correctness, harness
from bench.tests import sizes
from bench.tests.test_bench_faults import _altered, _frozen

if sys.argv[2] != "sound":
    from repro.serving.engine import Replica
    fault = {"token_altered": _altered, "state_unchanged": _frozen}[sys.argv[2]]
    Replica._step_paged_impl = fault(Replica._step_paged_impl)

warmed, layer, sampled = {}, {}, {}
real_warm_up, real_end_to_end = harness.warm_up, harness.end_to_end
real_sample = correctness.sample


def warm_up(replicas, spec, seed, vocab):
    real_warm_up(replicas, spec, seed, vocab)
    for rep in replicas:
        warmed[rep.name] = {"device": rep.device.id,
                            "prefilled": rep.prefilled_tokens,
                            "decode_steps": rep.counters.decode_steps}


def end_to_end(run, horizon_ms, setup_s):
    layer.update(harness.per_layer(
        ["route_max_share", "prefix_hit_share", "queue_depth_mean.ttft"], run))
    return real_end_to_end(run, horizon_ms, setup_s)


def sample(records, *a):
    out = real_sample(records, *a)
    sampled["served"] = sorted({r["replica"] for r in records if r["ok"]})
    sampled["compared"] = sorted({r["replica"] for r in out})
    return out


harness.warm_up, harness.end_to_end = warm_up, end_to_end
correctness.sample = sample
spec = sizes.small_sessions("sessions-fleet4")
spec["session_rate_per_s"] = 2.0
# the cell as BENCHMARK.json would list it
bench = sizes.bench()
bench["workloads"].append({"name": sys.argv[1], "config": "qwen3-4b-x4",
                           "traffic": "sessions-fleet4", "chips": 4})
out = harness.run_cell(sys.argv[1], 2**31 + 17, 4.0, False,
                       t_start=time.monotonic(), require_tpu=False,
                       bench=bench, config=sizes.small_qwen("qwen3-4b-x4"),
                       spec=spec)
print(json.dumps({"devices": len(jax.devices()), "warmed": warmed,
                  "layer": layer, "sampled": sampled, "correct": out["correct"],
                  "attempted": out["attempted"], "failed": out["failed"],
                  "count": out["device"]["count"],
                  "compared": out["compared"]}))
"""


def _run(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(harness.ROOT / "src"),
                                           str(harness.ROOT)]))
    p = subprocess.run([sys.executable, "-c", CHILD, CELL, fault],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_replicas_warm_route_and_serve_correctly():
    got = _run("sound")
    assert got["devices"] == 4 and got["count"] == 4
    # one replica per device, each warmed before the window opened
    warmed = got["warmed"]
    assert sorted(w["device"] for w in warmed.values()) == [0, 1, 2, 3]
    assert all(w["prefilled"] > 0 and w["decode_steps"] > 0
               for w in warmed.values())
    assert got["attempted"] > 0 and got["failed"] == 0
    assert got["correct"] is True
    gap = got["compared"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    # the sample compares a request of every replica that finished one
    assert got["sampled"]["compared"] == got["sampled"]["served"]
    layer = got["layer"]
    assert layer["route_max_share"] >= 1.0
    assert 0.0 <= layer["prefix_hit_share"] <= 1.0


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_step_on_the_fleet_is_not_correct(fault):
    got = _run(fault)
    gap = got["compared"]["max_logit_gap"]
    assert got["correct"] is False
    assert gap["value"] > gap["limit"]


def _rec(i, replica, prompt, n_out):
    return {"id": i, "replica": replica, "ok": True, "n_out": n_out,
            "prompt": np.zeros((prompt,), np.int32)}


def test_sample_holds_a_request_of_every_replica():
    # replica0 finished every long request: limits of one request or a few
    # tokens would stop a sample drawn by length and seed alone there
    recs = [_rec(i, "replica0", 3000, 400) for i in range(20)]
    recs += [_rec(100 + i, f"replica{1 + i % 3}", 64, 16) for i in range(9)]
    for seed in (1, 2**31 + 5, 2**40 + 3):
        got = correctness.sample(recs, seed, tokens=400, max_requests=1)
        assert {r["replica"] for r in got} == {f"replica{k}" for k in range(4)}
        assert len(got) == 4 and got[0]["prompt"].size == 3000
        assert len({r["id"] for r in got}) == 4
    # one replica: the sample is the one the limits give, as before
    one = [r for r in recs if r["replica"] == "replica0"]
    got = correctness.sample(one, 7, tokens=1000, max_requests=4)
    assert len(got) == 3 and sum(r["n_out"] for r in got) == 1200
