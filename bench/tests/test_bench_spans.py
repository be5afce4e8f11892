"""The program's spans and scopes in a trace (``bench/spans.py``): the idle
split among the host spans, and the device time of the chunk's scoped ops,
whose paths come from the compiled chunk's metadata."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans
from bench import trace as tr
from bench.tests import tiny

FIXTURE = Path(__file__).with_name("data") / "run_simulation_small.xplane.pb.gz"
DEV = "/device:TPU:0"
NEW_METRICS = ("chunk_traces_per_call", "retrace_idle_share", "init_idle_share",
               "round_trip_idle_share", "vaoi_proxy_device_share", "train_device_ms_per_epoch",
               "slot_scan_device_ms_per_epoch", "fedavg_device_ms_per_epoch")


def _trace(ops, host, window=(0.0, 10.0), modules=()):
    """A trace of one device: ``ops`` as ``(start, end)`` or ``(instruction
    text, start, end)``, ``host`` as ``(name, start, end, depth)``."""
    ops = [op if len(op) == 3 else ("%op = f32[] add()", *op) for op in ops]
    return tr.Trace(window=window, ops={DEV: ops}, modules={DEV: list(modules)}, host=list(host))


def _idle_s(t: tr.Trace) -> float:
    """Idle seconds by ``device_idle_share``'s arithmetic."""
    busy = tr.busy_s(t)
    return t.window_s - sum(busy.values()) / len(busy)


def test_gap_straddling_two_spans_is_split_by_overlap():
    # device busy [0, 1] and [5, 10]; the gap [1, 5] runs 1-3 in init_carry,
    # 3-4.5 in a chunk that traced, 4.5-5 outside any span
    t = _trace([(0, 1), (5, 10)], [
        (spans.INIT_SPAN, 0.5, 3.0, 0),
        (spans.CHUNK_SPAN, 3.0, 4.5, 0),
        (spans.TRACE_CHUNK_SPAN, 3.2, 4.0, 1),
    ])
    got = spans.idle_by_span(t)
    assert got["init_carry"] == pytest.approx(2.0)
    assert got["chunk_traced"] == pytest.approx(1.5)
    assert got["unattributed"] == pytest.approx(0.5)
    assert got["eval"] == got["chunk_untraced"] == 0.0


def test_innermost_span_wins():
    t = _trace([(0, 1), (9, 10)], [
        (spans.CHUNK_SPAN, 1.0, 9.0, 0),
        (spans.EVAL_SPAN, 3.0, 5.0, 1),
        ("bench.call", 0.0, 10.0, 0),  # not a program span
    ])
    got = spans.idle_by_span(t)
    assert got["eval"] == pytest.approx(2.0)
    assert got["chunk_untraced"] == pytest.approx(6.0)
    assert got["unattributed"] == pytest.approx(0.0, abs=1e-12)


def test_traced_and_untraced_chunks():
    t = _trace([(0, 1), (2, 3), (4, 5), (9, 10)], [
        (spans.CHUNK_SPAN, 1.0, 2.0, 0),
        (spans.TRACE_CHUNK_SPAN, 1.1, 1.5, 1),
        (spans.CHUNK_SPAN, 3.0, 4.0, 0),
        (spans.EVAL_SPAN, 5.0, 9.0, 0),
    ])
    got = spans.idle_by_span(t)
    assert got["chunk_traced"] == pytest.approx(1.0)
    assert got["chunk_untraced"] == pytest.approx(1.0)
    assert got["eval"] == pytest.approx(4.0)


def test_buckets_sum_to_the_idle_time():
    ops = [(0.2, 0.7), (0.5, 1.4), (2.0, 2.1), (2.05, 3.3), (6.0, 6.5), (8.0, 9.5)]
    host = [(spans.INIT_SPAN, 0.0, 0.9, 1), (spans.CHUNK_SPAN, 1.0, 2.5, 1),
            (spans.TRACE_CHUNK_SPAN, 1.2, 1.9, 2), (spans.EVAL_SPAN, 2.5, 4.0, 1),
            (spans.CHUNK_SPAN, 4.0, 4.2, 1), (spans.EVAL_SPAN, 4.2, 7.0, 1),
            ("bench.call", 0.0, 9.0, 0)]
    t = _trace(ops, host, window=(0.1, 9.8))
    got = spans.idle_by_span(t)
    assert set(got) == set(spans.BUCKETS)
    assert sum(got.values()) == pytest.approx(_idle_s(t), abs=1e-12)
    assert min(got.values()) >= 0


def test_buckets_sum_to_the_idle_time_of_a_chip_trace(chip_trace):
    """The committed trace has no program spans: all its idle time is
    unattributed, and the buckets still sum to it."""
    got = spans.idle_by_span(chip_trace)
    assert got["unattributed"] == pytest.approx(_idle_s(chip_trace), rel=1e-9)
    assert sum(got.values()) - got["unattributed"] == 0.0


PATHS = {
    "while.1": "jit(chunk)/while",  # the epoch loop: unscoped
    "while.2": "jit(chunk)/while/body/ehfl.local_train/while",
    "fusion.3": "jit(chunk)/while/body/ehfl.local_train/vmap()/while/body/conv",
    "fusion.4": "jit(chunk)/while/body/ehfl.local_train/vmap()/while/body/ehfl.eq6_moment/dot",
    "while.5": "jit(chunk)/while/body/ehfl.slot_scan/while",
    "add.6": "jit(chunk)/while/body/not.ehfl.fedavg_x/add",
    "copy.7": "",
}


def test_scope_union_counts_a_loop_and_its_body_once():
    ops = {DEV: [("while.1", 0.0, 10.0), ("while.2", 1.0, 5.0), ("fusion.3", 1.5, 2.0),
                 ("fusion.4", 2.0, 2.5), ("while.5", 6.0, 6.25), ("add.6", 7.0, 8.0),
                 ("copy.7", 8.0, 9.0)]}
    w = (0.0, 10.0)
    assert spans.scope_s(ops, PATHS, ["ehfl.local_train"], w) == pytest.approx(4.0)
    assert spans.scope_s(ops, PATHS, ["ehfl.eq6_moment"], w) == pytest.approx(0.5)
    assert spans.scope_s(ops, PATHS, ["ehfl.local_train", "ehfl.eq6_moment"], w) == pytest.approx(4.0)
    assert spans.scope_s(ops, PATHS, ["ehfl.slot_scan"], w) == pytest.approx(0.25)
    assert spans.scope_s(ops, PATHS, ["ehfl.fedavg"], w) == 0.0
    both = ["ehfl.local_train", "ehfl.slot_scan"]
    assert spans.scope_s(ops, PATHS, both, (4.0, 6.125)) == pytest.approx(1.125)


def test_module_ops_keep_the_ops_of_the_named_executable():
    """Instruction names repeat across executables: only the ops that ran
    inside a run of the matching executable are kept, by name."""
    t = _trace([("%fusion.3 = f32[2] fusion(f32[2] %p), kind=kLoop", 1.0, 2.0),
                ("%fusion.3 = f32[4] fusion(f32[4] %q), kind=kLoop", 5.0, 5.5),
                ("%while.1 = (s32[]) while((s32[]) %t)", 0.5, 4.0)],
               [], modules=[("jit_chunk(7)", 0.5, 4.0), ("jit__lambda(9)", 5.0, 6.0)])
    assert spans.module_ops(t, "^jit_chunk") == {DEV: [("fusion.3", 1.0, 2.0), ("while.1", 0.5, 4.0)]}
    assert spans.module_ops(t, "^jit__lambda") == {DEV: [("fusion.3", 5.0, 5.5)]}


def test_module_ops_on_the_chip_trace(chip_trace):
    ops = spans.module_ops(chip_trace, "^jit_chunk")[DEV]
    runs = [(s, t) for name, s, t in chip_trace.modules[DEV] if name.startswith("jit_chunk")]
    assert 0 < len(ops) < len(chip_trace.ops[DEV])
    assert all(any(a <= s < b for a, b in runs) for _, s, _ in ops)
    assert all(" " not in name and not name.startswith("%") for name, _, _ in ops)


def test_hlo_op_paths_of_a_compiled_module():
    """Every instruction of the compiled text, with the name stack of its
    ``op_name``: a scope inside a loop body leaves the loop op unscoped."""
    import jax
    import jax.numpy as jnp

    def f(x):
        def step(c, _):
            with jax.named_scope("ehfl.local_train"):
                return jnp.sin(c) * 2.0, None
        with jax.named_scope("ehfl.slot_scan"):
            x = jnp.cumsum(x)
        return jax.lax.scan(step, x, None, length=3)[0]

    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    paths = spans.hlo_op_paths(text)
    assert len(paths) == len(spans._INSTRUCTION.findall(text))
    segs = lambda p: p.split("/")
    assert any("ehfl.local_train" in segs(p) for p in paths.values())
    assert any("ehfl.slot_scan" in segs(p) for p in paths.values())
    loops = [p for n, p in paths.items() if n.startswith("while")]
    assert loops and all("ehfl.local_train" not in segs(p) for p in loops)
    assert spans.hlo_op_paths('  ROOT %t.1 = (f32[]) tuple(%a), metadata={op_name="a/\\"b\\"/c"}\n'
                              "  %b.2 = f32[] add(%a, %a)\n") == {"t.1": 'a/\\"b\\"/c', "b.2": ""}


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("spans"))
    return harness.load_cell(root, "tiny_vaoi")


def test_chunk_op_paths_are_those_of_the_run(tiny_cell):
    """The chunk compiled from abstract arguments names its instructions as
    the run's chunk does, and carries every phase's scope; the cache-key
    setting it flips is put back."""
    import jax
    import jax.numpy as jnp
    from repro.core import simulator

    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    paths = spans.chunk_op_paths(tiny_cell)
    assert getattr(jax.config, key) == before
    cfg = harness.sim_config(tiny_cell, 3, tiny_cell.traffic["horizon"])
    be = harness.backend(tiny_cell)
    data = tiny_cell.family.make_dataset(3, tiny_cell.config["data"], tiny_cell.config["model"])
    ran = simulator.chunk_program(cfg, be).lower(
        simulator.init_carry(cfg, be), jnp.arange(cfg.eval_every), data["images"], data["labels"])
    assert spans.hlo_op_paths(ran.compile().as_text()) == paths
    segs = {seg for p in paths.values() for seg in p.split("/")}
    assert {"ehfl.vaoi_proxy", "ehfl.slot_scan", "ehfl.local_train", "ehfl.eq6_moment",
            "ehfl.fedavg"} <= segs


def _scope_ctx(cell, names, epochs=4):
    """A run's context whose trace ran the chunk instructions ``names``, one
    after another for a second each, inside one chunk span."""
    ops = [(f"%{n} = f32[] add(f32[] %a, f32[] %b)", float(i), i + 1.0) for i, n in enumerate(names)]
    host = [(spans.CHUNK_SPAN, 0.0, len(names), 0)]
    t = _trace(ops, host, window=(0.0, float(len(names))),
               modules=[("jit_chunk(1)", 0.0, float(len(names)))])
    return SimpleNamespace(cell=cell, trace=t, epochs=epochs, n_calls=1)


def test_scope_readers_read_the_chunk_ops(tiny_cell, monkeypatch):
    calls = []
    monkeypatch.setattr(spans, "chunk_op_paths", lambda cell: calls.append(cell) or PATHS)
    ctx = _scope_ctx(tiny_cell, ["while.2", "fusion.4", "while.5", "add.6", "copy.7"])
    read = lambda m: harness.load_reader(tiny_cell, m)(ctx)
    assert read("train_device_ms_per_epoch") == pytest.approx(1e3 * 2 / 4)
    assert read("slot_scan_device_ms_per_epoch") == pytest.approx(1e3 * 1 / 4)
    assert read("vaoi_proxy_device_share") == pytest.approx(100.0 * 1 / 5)
    assert read("fedavg_device_ms_per_epoch") is None  # the paths have no FedAvg scope
    assert len(calls) == 1  # compiled once per run


def test_scope_readers_raise_on_a_chunk_without_scopes(tiny_cell, monkeypatch):
    """A program that opens its spans but whose compiled chunk names no
    phase (as an executable cached from another program would) fails the
    run instead of dropping the metrics."""
    monkeypatch.setattr(spans, "chunk_op_paths", lambda cell: {"while.1": "jit(chunk)/while"})
    with pytest.raises(ValueError, match="names no"):
        spans.chunk_scope_s(_scope_ctx(tiny_cell, ["while.1"]), ["ehfl.local_train"])


def test_scope_readers_raise_on_ops_the_compiled_chunk_lacks(tiny_cell, monkeypatch):
    monkeypatch.setattr(spans, "chunk_op_paths", lambda cell: PATHS)
    with pytest.raises(ValueError, match="lacks"):
        spans.chunk_scope_s(_scope_ctx(tiny_cell, ["while.2", "fusion.99"]), ["ehfl.local_train"])


@pytest.fixture(scope="module")
def chip_trace():
    return tr.load(str(FIXTURE))


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_fall_silent_on_a_program_without_spans(metric, chip_trace):
    """The committed trace was recorded from a program that opens no span
    and names no scope: every new reader returns nothing there."""
    cell = harness.load_cell(tiny.REPO, "paper_vaoi")
    ctx = SimpleNamespace(cell=cell, trace=chip_trace, n_calls=2, epochs=20,
                          events=harness.Events(), window_s=chip_trace.window_s)
    assert harness.load_reader(cell, metric)(ctx) is None


def test_new_metrics_in_a_traced_run(tmp_path, monkeypatch):
    """A traced CPU run of the tiny cell with the new metrics listed for it:
    the retrace counter reads the program's own count of chunk traces over
    the window's calls, at most one per call (none where the program keeps
    its chunk across calls); the device metrics find no device ops on the
    CPU and are left out."""
    runs = []

    class Kept(harness.Events):
        def __init__(self):
            super().__init__()
            runs.append(self)

    monkeypatch.setattr(harness, "Events", Kept)
    root = tiny.make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny_vaoi")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    result = harness.run(harness.load_cell(root, "tiny_vaoi"), 2**31 + 7, 0.5, True, 0.0,
                         require_chip=False)
    assert result["correct"], result["checks"]
    value = result["metrics"]["chunk_traces_per_call"]["value"]
    assert value == runs[-1].counts.get(spans.CHUNK_TRACE_EVENT, 0) / result["attempted"]
    assert 0.0 <= value <= 1.0
    assert not set(NEW_METRICS[1:]) & set(result["metrics"])
