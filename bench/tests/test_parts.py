"""Self-checks of the yardstick's parts: the generator, the trace
reduction, the peaks table, the qwen reference, and the format of
``BENCHMARK.json``."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchlib import harness, peaks, qwen_ref, readers, trace, traffic

ROOT = harness.ROOT


def test_poisson_offer_is_seeded_and_at_rate():
    mix = {"arrivals": "poisson", "rate_msgs_per_s": 50_000}
    a = traffic.Offer(mix, 16, 2 ** 40 + 3, 2.0)
    b = traffic.Offer(mix, 16, 2 ** 40 + 3, 2.0)
    c = traffic.Offer(mix, 16, 7, 2.0)
    assert all(np.array_equal(x, y) for x, y in zip(a.due, b.due))
    assert not np.array_equal(a.due[0], c.due[0])
    total = sum(len(d) for d in a.due)
    assert abs(total - 100_000) < 5 * np.sqrt(100_000)
    for d in a.due:                     # an equal share for every sender
        assert abs(len(d) - 100_000 / 16) < 5 * np.sqrt(100_000 / 16)
    got = a.take(1.0, np.zeros(16)) + a.take(2.0, np.zeros(16))
    assert got.sum() == total and (a.lateness() >= 0).all()


def test_independent_and_onoff_offers():
    ind = traffic.Offer({"arrivals": "poisson",
                         "rate_msgs_per_s": 16_000}, 16, 1, 1.0)
    assert abs(sum(len(d) for d in ind.due) - 16_000) < 800
    assert not np.array_equal(ind.due[0][:5], ind.due[1][:5])
    on = traffic.Offer({"arrivals": "onoff", "rate_on": 2000,
                        "rate_off": 0, "mean_on_s": 0.05,
                        "mean_off_s": 0.05, "senders": [0, 3]}, 4, 1, 1.0)
    assert len(on.due[1]) == 0 and len(on.due[0]) > 0


def test_backlogged_offer_tops_up():
    off = traffic.Offer({"arrivals": "backlogged", "top_up": 200}, 4, 1,
                        1.0)
    assert off.take(0.0, np.array([0, 50, 200, 250])).tolist() == \
        [200, 150, 0, 0]
    assert off.lateness() is None


def test_union_and_idle_gaps_by_hand():
    ops = {"/device:TPU:0": [(10, 20, "a"), (15, 30, "b"), (50, 60, "a")]}
    mods = {"/device:TPU:0": [(10, 30, "jit_fn"), (50, 60, "jit_fn")]}
    spans = [(0, 100, "bench.window"), (0, 45, "bench.step"),
             (45, 100, "bench.traffic")]
    r = trace.reduce_events(ops, mods, spans)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(30e-9)
    assert r.idle_share == pytest.approx(0.7)
    assert r.modules == {"jit_fn": (2, pytest.approx(30e-9))}
    assert dict(r.device_ops) == {"a": pytest.approx(20e-9),
                                  "b": pytest.approx(15e-9)}
    # gaps: [0,10] and [30,50] under bench.step (midpoints 5, 40);
    # [60,100] under bench.traffic
    assert dict(r.idle_gaps) == {"bench.step": pytest.approx(30e-9),
                                 "bench.traffic": pytest.approx(40e-9)}
    assert trace.reduce_events({}, {}, spans) is None


def test_reduction_of_a_trace_recorded_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.traffic"):
                np.linalg.svd(np.ones((100, 100)))
    jax.profiler.stop_trace()
    r = trace.reduce_trace(str(tmp_path), device_prefix="/host:CPU",
                           op_line="tf_XLAPjRtCpuClient")
    assert r is not None and 0 < r.busy_s < r.window_s
    assert any("dot" in name for name, _ in r.device_ops)
    assert {name for name, _ in r.idle_gaps} <= {
        "bench.step", "bench.traffic", "bench.window"}


def test_stream_program_is_read_by_its_name():
    red = trace.Reduced(window_s=1.0, busy_s=0.1, n_devices=1,
                        modules={"jit_fn(35771)": (10, 1e-4),
                                 "jit_helper(12)": (40, 1.0)},
                        device_ops=[], idle_gaps=[])
    run = harness.Run(setup_s=1.0, window_s=1.0, attempted=1, failed=0,
                      checks={}, values={"stream_program": "jit_fn",
                                         "window_rounds": 11},
                      trace=red)
    assert readers.stream_program_us(run) == pytest.approx(10.0)
    run.values["window_rounds"] = 7          # more executions than rounds
    with pytest.raises(RuntimeError):
        readers.stream_program_us(run)
    run.values["window_rounds"] = 40         # far fewer
    with pytest.raises(RuntimeError):
        readers.stream_program_us(run)
    run.values.update(stream_program="jit_gone", window_rounds=10)
    with pytest.raises(RuntimeError):
        readers.stream_program_us(run)
    run.trace = None
    assert readers.stream_program_us(run) is None


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_qwen_reference_agrees_with_the_program_prefill():
    """The float32 reference against the program's own full-sequence
    prefill at the reduced size, on weights drawn from the seed."""
    harness.import_program()
    import jax
    from repro.models import registry, transformer
    from repro.models.runtime import Runtime

    sizes = harness.load_json(os.path.join(harness.BENCH, "tools",
                                           "qwen1.5-0.5b.json"))
    cfg = registry.get("qwen1.5-0.5b").cfg.reduced()
    sz = dict(sizes, **sizes["tiny"], rope_theta=cfg.rope_theta)
    w = qwen_ref.draw_weights(sz, 5)            # bf16, as served
    spec = jax.tree.map(lambda s: tuple(s.shape),
                        registry.param_specs(cfg),
                        is_leaf=lambda s: hasattr(s, "axes"))
    assert jax.tree.map(lambda x: tuple(x.shape), w) == spec
    toks = np.random.default_rng(0).integers(1, 512, 24).astype(np.int32)
    ref = qwen_ref.Reference(sz, w).logits(toks)[-1]
    got = np.asarray(transformer.prefill(w, cfg, toks[None], Runtime())[0][0],
                     np.float32)
    # the program rounds every op to bf16 (8 bits of mantissa): over two
    # layers its logits track the float32 reference closely, not exactly
    assert np.corrcoef(got, ref)[0, 1] > 0.99
    assert int(np.argmax(got)) in np.argsort(ref)[-3:]


def test_benchmark_json_keeps_its_format():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    assert {w["config"] for w in bench["workloads"]} == configs
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/") and name.match(c["name"])
    for w in bench["workloads"]:
        assert name.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(
            harness.BENCH, "traffic", w["traffic"] + ".json"))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        moves = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moves.get("workloads", cells)
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m["workloads"] for m in bench["per_layer"])
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_without_a_chip_the_run_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "testbed.saturated", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
