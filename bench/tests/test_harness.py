"""The harness's own arithmetic, on the CPU: every cell of
``BENCHMARK.json`` resolves its files by name and keeps to the driver
contract, the least-work counts match hand counts at the cells' shapes,
and the trace reduction gives the busy time, idle gaps, each op's program
and the per-op sums of a small trace recorded on a TPU v5e
(``data/serve_small.xplane.pb.gz``: a serve window of the chunked-AE q8 codec
at K=16, N=4096, made by ``record_trace.py``)."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, peaks, trace, work  # noqa: E402

TRACE_GZ = os.path.join(os.path.dirname(__file__), "data",
                        "serve_small.xplane.pb.gz")
SERVE_CELLS = ("serve_chunkae_k1024", "serve_fcae_k256",
               "serve_chunkae_n1m_k64")
CIFAR_CONFIGS = ("cifar_chunkae_q8", "cifar_fcae")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def chip_trace_path(tmp_path_factory):
    """The recorded trace, unpacked (it is kept gzipped)."""
    import gzip
    import shutil
    path = tmp_path_factory.mktemp("trace") / "serve_small.xplane.pb"
    with gzip.open(TRACE_GZ, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def chip_trace(chip_trace_path):
    return trace.load(chip_trace_path)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_resolves_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]
                                           ["file"]))
        traffic = harness.load_json("traffic", w["traffic"] + ".json")
        harness.load_module("drivers", traffic["driver"])
        limits = harness.load_json("limits", w["name"] + ".json")
        if w["name"] in SERVE_CELLS:
            assert set(limits) == {"state_mismatch", "times_err", "agg_err"}
    assert SERVE_CELLS == tuple(w["name"] for w in bench["workloads"]
                                if w["name"] in SERVE_CELLS)
    for m in bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert set(m["workloads"]) <= {w["name"] for w in bench["workloads"]}


def test_names_and_bounds_keep_to_the_contract(bench):
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {"setup_s"} <= {m["name"] for m in bench["end_to_end"]}
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])


def test_config_file_is_the_config_as_run(bench):
    for c in bench["configs"]:
        cfg = harness.load_json("configs", c["name"] + ".json")
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        if c["name"] in CIFAR_CONFIGS:
            assert cfg["model"]["update_size"] == 550_586
    serve = harness.load_module("drivers", "serve")
    for name in CIFAR_CONFIGS:
        cfg = harness.load_json("configs", name + ".json")
        assert serve.update_size(cfg) == 550_586


def test_the_benchmark_keeps_the_driver_contract(bench):
    """Each cell's limits are its driver's ``CHECKS``, each configuration
    states the update size its driver counts, and each per-layer metric
    lists cells that exist."""
    assert harness.contract_problems(bench) == []


CHUNK = harness.codec_of(harness.load_json("configs",
                                           "cifar_chunkae_q8.json"))
FC = harness.codec_of(harness.load_json("configs", "cifar_fcae.json"))


def test_least_work_chunked_ae_by_hand():
    # 135 chunks of 4096; decoder 8 -> 512 (relu) -> 4096 (linear)
    k = 1024
    hidden = k * 2 * 135 * 8 * 512            # per client, latent side
    reduce = 2 * k * 135 * 512                # weighted sum of hidden rows
    last = 2 * 135 * 512 * 4096               # last layer once
    assert work.stage_flops(CHUNK, k) == hidden + reduce + last
    assert work.stage_flops(CHUNK, k) == 1_840_250_880
    # payload: 1080 latents -> 17 blocks of 64 int8 codes + 17 f32 scales
    assert work.payload_bytes(CHUNK) == 17 * 64 + 17 * 4 == 1156
    params = 4 * (8 * 512 + 512 + 512 * 4096 + 4096)
    assert work.decoder_param_bytes(CHUNK) == params == 8_423_424
    assert work.stage_bytes(CHUNK, 550_586, k) == \
        k * 1156 + params + 4 * 550_586
    queue = 100_000 * 8 + k * 4 + k * 12
    assert work.round_bytes(CHUNK, 550_586, 100_000, k) == \
        k * 1156 + params + queue + 2 * 4 * 550_586 == 14_828_240


def test_least_work_fc_ae_by_hand():
    # decoder 320 -> 550,586 (linear): reduce the latents, then one matvec
    k = 256
    assert work.stage_flops(FC, k) == 2 * k * 320 + 2 * 320 * 550_586
    assert work.payload_bytes(FC) == 320 * 4
    assert work.decoder_param_bytes(FC) == 4 * 550_586 * 321 == 706_952_424
    assert work.stage_bytes(FC, 550_586, k) == \
        k * 1280 + 706_952_424 + 4 * 550_586


def test_least_time_takes_the_larger_bound():
    kind = "TPU v5 lite"
    assert peaks.least_seconds(197e12, 0.0, kind) == pytest.approx(1.0)
    assert peaks.least_seconds(0.0, 819e9, kind) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.least_seconds(1.0, 1.0, "TPU v4")


def _sweep(tr):
    """Busy time and per-op sums, by ``(program, name)``, by another route:
    a sweep over every event boundary inside the window with a count of
    open events."""
    lo, hi = tr.window()
    evs = [(max(e.start_ns, lo), min(e.end_ns, hi), (e.program, e.name))
           for e in tr.ops[0] if min(e.end_ns, hi) > max(e.start_ns, lo)]
    marks = sorted([(s, 1) for s, _, _ in evs] + [(t, -1) for _, t, _ in evs])
    busy, open_, last = 0.0, 0, lo
    for t, d in marks:
        if open_ > 0:
            busy += t - last
        open_ += d
        last = t
    sums = {}
    for s, t, n in evs:
        sums[n] = sums.get(n, 0.0) + (t - s)
    return busy, sums


def test_trace_reduction_on_a_chip_trace(chip_trace):
    tr = chip_trace
    assert len(tr.ops) == 1 and tr.ops[0]
    s = trace.summarize(tr)
    busy, sums = _sweep(tr)
    assert s.busy_s == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < s.busy_s <= s.window_s
    lo, hi = tr.window()
    idle = sum(b - a for a, b in trace.gaps(s.ops[0], lo, hi))
    assert (idle + busy) * 1e-9 == pytest.approx(s.window_s, rel=1e-9)
    top = dict((n, v) for n, v in s.breakdown["device_ops"])
    programs = {}
    for p, n in sums:
        programs.setdefault(n, []).append(p)
    for label, v in top.items():
        # a name that one program has stands alone; a shared one is
        # ``program:name``
        p, _, n = label.rpartition(":")
        if p:
            assert len(programs[n]) > 1
        else:
            assert len(programs[n]) == 1
            p = programs[n][0]
        assert v == pytest.approx(sums[p, n] * 1e-9, rel=1e-9)
    assert sum(sums.values()) * 1e-9 >= s.busy_s * (1 - 1e-9)
    assert all(g[0].startswith("bench.") or g[0] == "none"
               for g in s.breakdown["idle_gaps"])
    assert len(s.breakdown["device_ops"]) <= 10


def test_metric_readers_on_a_chip_trace(bench, chip_trace):
    """Every per-layer metric of a chunked-AE serve cell reads the recorded
    trace (K=16, N=4096), and no share reads above 100%."""
    tr = chip_trace
    lo, hi = tr.window()
    rounds = sum(1 for s in tr.spans
                 if s.name == "bench.round" and lo <= s.start_ns <= hi)
    w = next(c for c in bench["workloads"]
             if c["name"] == "serve_chunkae_k1024")
    cell = harness.make_cell(w, 1, 1.0, True, 0.0)
    cell.traffic.update(population=4096, buffer_k=16)
    outcome = harness.Outcome(
        attempted=rounds, failed=0, checks=[], end_to_end={},
        counters={"rounds": rounds, "buffer_k": 16, "population": 4096},
        memory_peak_bytes=0, summary=trace.summarize(tr))
    ctx = harness.MetricContext(cell, outcome, "TPU v5 lite")
    for m in bench["per_layer"]:
        if w["name"] not in m["workloads"]:
            continue
        value = harness.load_module("metrics", m["name"]).read(ctx)
        assert value is not None and value > 0.0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)


def test_each_op_keeps_its_program(chip_trace, chip_trace_path):
    """Every device op carries the program whose run on the ``XLA
    Modules`` line contains it, found here by a scan over every run; in
    this window the step and the harness's copies share instruction names
    (``copy-done``), which the breakdown then puts apart."""
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(chip_trace_path).planes
                 if p.name.startswith("/device:TPU:"))
    runs = [(e.start_ns, e.start_ns + e.duration_ns, e.name.split("(")[0])
            for line in plane.lines if line.name == "XLA Modules"
            for e in line.events]
    ops = chip_trace.ops[0]
    for e in ops:
        inside = [p for a, b, p in runs if a <= e.start_ns and e.end_ns <= b]
        assert inside == [e.program], (e, inside)
    assert {e.program for e in ops} == {"jit_step", "jit__lambda"}
    by_name = {}
    for e in ops:
        by_name.setdefault(e.name, set()).add(e.program)
    assert by_name["copy-done"] == {"jit_step", "jit__lambda"}
    assert all(p == {"jit_step"} for n, p in by_name.items()
               if n.startswith("fused_dense"))


def test_shared_names_are_put_apart_by_program():
    """Ops of two programs with one instruction name are summed and named
    apart; a name that one program has keeps its bare name."""
    ev = trace.Event
    tr = trace.Trace(
        ops=[[ev("fusion.3", 0, 10, "jit_a"), ev("fusion.3", 20, 30, "jit_b"),
              ev("sort", 60, 5, "jit_a"), ev("fusion.3", 70, 10, "jit_a")]],
        spans=[ev("bench.window", 0, 100)], runs=[[]])
    s = trace.summarize(tr)
    names, seconds = zip(*s.breakdown["device_ops"])
    assert names == ("jit_b:fusion.3", "jit_a:fusion.3", "sort")
    assert seconds == pytest.approx((30e-9, 20e-9, 5e-9))
    assert s.op_seconds(lambda n: n == "fusion.3") == pytest.approx(50e-9)
    assert s.program_op_seconds(
        lambda p, n: (p, n) == ("jit_a", "fusion.3")) == pytest.approx(20e-9)
