"""The host spans, retrace counter and the epoch body's named scopes
(README "Tracing"): what a profiler trace or a monitoring listener sees of a
tiny run, and which scopes each epoch program carries in its op names.
Between those spans, ``drive_epochs`` only dispatches: no device value
reaches the host while its chunk loop runs, and the F1 trajectory it keeps
on the device is the eager macro-F1 of each chunk's global model."""
import collections
import contextlib
import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

from repro.configs.cifar_cnn import CNNConfig
from repro.core import EHFLConfig, fleet, run_fleet, run_simulation
from repro.core import simulator
from repro.data import make_federated_dataset
from repro.fl import cnn_backend
from repro.models.cnn import macro_f1

TINY_CNN = CNNConfig(name="tiny", image_size=8, conv_channels=(4, 4, 8, 8, 8, 8), fc_dims=(16, 8))
N = 8
SCOPES = ("ehfl.vaoi_proxy", "ehfl.slot_scan", "ehfl.local_train", "ehfl.eq6_moment", "ehfl.fedavg")
VAOI_ONLY = ("ehfl.vaoi_proxy", "ehfl.eq6_moment")


@pytest.fixture(scope="module")
def backend():
    return cnn_backend(TINY_CNN)


@pytest.fixture(scope="module")
def world():
    return make_federated_dataset(
        jax.random.PRNGKey(0), num_clients=N, samples_per_client=24,
        alpha=0.5, test_size=40, image_size=8,
    )


def _cfg(**kw):
    base = dict(num_clients=N, epochs=8, slots_per_epoch=10, kappa=4, p_bc=0.5, k=3,
                mu=0.1, e_max=6, eval_every=4, probe_size=4)
    return EHFLConfig(**{**base, **kw})


class _Count:
    def __init__(self):
        self.counts = collections.Counter()

    def __call__(self, event, **_):
        self.counts[event] += 1


def test_one_chunk_trace_event_per_call(backend, world):
    """``run_simulation`` builds a fresh jitted chunk per call, so every call
    traces it once (both chunks of a call share the trace)."""
    count = _Count()
    jax.monitoring.register_event_listener(count)
    try:
        for seed in (0, 1):
            jax.block_until_ready(run_simulation(_cfg(seed=seed), backend, world))
            assert count.counts[simulator.CHUNK_TRACE_EVENT] == seed + 1
    finally:
        jax.monitoring.unregister_event_listener(count)


def _scopes_in(lowered) -> set:
    return set(re.findall(r"ehfl\.[a-z0-9_]+", lowered.as_text(debug_info=True)))


def _solo_chunk(cfg, backend, world):
    """Lower ``run_simulation``'s jitted chunk without running it."""
    return simulator.chunk_program(cfg, backend).lower(
        simulator.init_carry(cfg, backend), jnp.arange(cfg.eval_every), world["images"],
        world["labels"])


@pytest.mark.parametrize("policy,compact", [("vaoi", "auto"), ("vaoi", False), ("fedavg", False)])
def test_epoch_program_carries_its_scopes(policy, compact, backend, world):
    scopes = _scopes_in(_solo_chunk(_cfg(policy=policy, compact=compact), backend, world))
    want = set(SCOPES) if policy == "vaoi" else set(SCOPES) - set(VAOI_ONLY)
    assert scopes == want


def test_fleet_program_carries_its_scopes(backend, world):
    cfg = _cfg()
    carry, scan_chunk, data, _ = fleet.fleet_program(cfg, backend, world)
    lowered = scan_chunk.lower(carry, jnp.arange(cfg.eval_every), data["images"], data["labels"])
    assert _scopes_in(lowered) == set(SCOPES)


def test_profiler_trace_holds_the_host_spans(tmp_path, backend, world):
    """A CPU profiler trace of one call, read as the benchmark reads it: one
    ``ehfl.init_carry``, one ``ehfl.trace_chunk`` (inside the first chunk
    span), and one ``ehfl.chunk`` and one ``ehfl.eval`` per chunk."""
    from bench import spans
    from bench import trace as tr

    cfg = _cfg(seed=5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            jax.block_until_ready(run_simulation(cfg, backend, world))
    finally:
        jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    names = collections.Counter(name for name, *_ in trace.host if name.startswith("ehfl."))
    chunks = cfg.epochs // cfg.eval_every
    assert names == {spans.INIT_SPAN: 1, spans.TRACE_CHUNK_SPAN: 1,
                     spans.CHUNK_SPAN: chunks, spans.EVAL_SPAN: chunks}
    assert spans.has_spans(trace)
    by = {n: sorted((s, t) for name, s, t, _ in trace.host if name == n)
          for n in (spans.INIT_SPAN, spans.TRACE_CHUNK_SPAN, spans.CHUNK_SPAN, spans.EVAL_SPAN)}
    (first_chunk, second_chunk), ((ts, tt),) = by[spans.CHUNK_SPAN], by[spans.TRACE_CHUNK_SPAN]
    assert first_chunk[0] <= ts and tt <= first_chunk[1]
    assert by[spans.INIT_SPAN][0][1] <= first_chunk[0]
    assert first_chunk[1] <= by[spans.EVAL_SPAN][0][0] <= by[spans.EVAL_SPAN][0][1] <= second_chunk[0]


@pytest.fixture
def events(monkeypatch):
    """In order: ``("enter" | "exit", span)`` for every host span, and
    ``("read", shape)`` for every host readback of a jax array (``float()``,
    ``int()``, ``np.asarray``, ``.item()`` and ``.tolist()`` all read
    ``ArrayImpl._value``)."""
    log = []
    # ``ArrayImpl._value`` is private: this patch point was checked against
    # JAX 0.9.0.  Should a later JAX read arrays back another way, the last
    # assertion of the test using this fixture fails rather than pass blind.
    value = inspect.getattr_static(jax_array.ArrayImpl, "_value")

    def read(self):
        log.append(("read", self.shape))
        return value.fget(self)

    annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(name, **kw):
        log.append(("enter", name))
        try:
            with annotation(name, **kw):
                yield
        finally:
            log.append(("exit", name))

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(read))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", span)
    return log


@pytest.mark.parametrize("driver", [run_simulation, run_fleet])
def test_no_readback_inside_the_chunk_loop(driver, events, backend, world):
    """From the first chunk's dispatch to the last eval's, the loop hands
    the device work and takes nothing back."""
    cfg = _cfg(seed=2)
    out = driver(cfg, backend, world)
    first = events.index(("enter", "ehfl.chunk"))
    last = len(events) - 1 - events[::-1].index(("exit", "ehfl.eval"))
    assert events.count(("enter", "ehfl.eval")) == cfg.epochs // cfg.eval_every
    assert [e for e in events[first:last + 1] if e[0] == "read"] == []
    float(out["metrics"]["f1"][-1])
    assert events[-1] == ("read", ()), "the readback probe does not see float()"


def test_f1_trajectory_is_the_eager_f1_of_each_chunk(backend, world):
    """``metrics["f1"]`` holds, per chunk, the eager ``macro_f1`` of the
    predictions for that chunk's global model: float32, one per eval, the
    short last chunk included."""
    cfg = _cfg(seed=3, epochs=6)
    scan = simulator.chunk_program(cfg, backend)
    params = []

    def scan_chunk(c, ts):
        c, ms = scan(c, ts, world["images"], world["labels"])
        params.append(jax.tree.map(jnp.copy, c.global_params))
        return c, ms

    out = simulator.drive_epochs(
        scan_chunk, simulator.init_carry(cfg, backend), cfg, backend, world)
    f1 = out["metrics"]["f1"]
    assert f1.dtype == jnp.float32 and f1.shape == (2,)
    np.testing.assert_array_equal(np.asarray(out["metrics"]["f1_epochs"]), [4, 6])
    predict = simulator._jitted_predict(backend.predict)
    want = [float(macro_f1(predict(p, world["test_images"]), world["test_labels"],
                           backend.num_classes)) for p in params]
    np.testing.assert_allclose(np.asarray(f1), want, rtol=0, atol=1e-7)
    assert max(want) > 0, "an F1 of zero throughout would not test the eval"
