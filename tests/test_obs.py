"""Structured telemetry (``repro.obs``): span nesting + monotonic timing,
histogram quantiles against numpy, JSONL schema round-trip through
``FileSink``, the near-zero disabled fast path, spans as profiler trace
events, the hook/loader/compile instrumentation, ``TrainLoop`` history
parity with the records it emits, and the end-to-end acceptance run — one
sink observing a CTDG epoch, a serving chaos burst, and a windowed
out-of-core storage epoch + streaming-CSR build, every record
schema-valid."""

import json
import time

import numpy as np
import pytest

from repro.obs import (
    FileSink,
    MemorySink,
    NullSink,
    Telemetry,
    bench_record,
    span_report,
    validate,
)
from repro.obs.telemetry import _H_GROWTH


# ------------------------------------------------------------------ spans

def test_span_nesting_and_monotonicity():
    tel = Telemetry()
    sink = tel.attach(MemorySink())
    with tel.span("outer", tag="x") as sp:
        with tel.span("inner"):
            time.sleep(0.002)
        sp["result"] = 7
    spans = [r for r in sink.records if r["kind"] == "span"]
    assert [s["path"] for s in spans] == ["outer.inner", "outer"]
    inner, outer = spans
    assert outer["name"] == "outer" and inner["name"] == "inner"
    # monotonic clock: inner starts after outer, outer spans inner
    assert inner["t0"] >= outer["t0"]
    assert outer["dur_s"] >= inner["dur_s"] > 0
    assert outer["attrs"] == {"tag": "x", "result": 7}
    for s in spans:
        validate(s)


def test_span_attrs_survive_exceptions():
    tel = Telemetry()
    sink = tel.attach(MemorySink())
    with pytest.raises(RuntimeError):
        with tel.span("boom") as sp:
            sp["partial"] = 1
            raise RuntimeError("x")
    (rec,) = sink.records
    assert rec["attrs"] == {"partial": 1}


def test_disabled_span_yields_writable_scratch():
    tel = Telemetry()  # no sinks: disabled
    assert not tel.enabled
    with tel.span("anything") as sp:
        sp["loss"] = 1.0  # must not raise
    tel.count("c")
    tel.gauge("g", 1.0)
    tel.observe("h", 0.1)
    assert tel.counter_value("c") == 0  # nothing recorded


def test_null_sink_keeps_telemetry_disabled():
    tel = Telemetry(NullSink())
    assert not tel.enabled


# -------------------------------------------------------------- histogram

def test_histogram_quantiles_vs_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)
    tel = Telemetry(MemorySink())
    for s in samples:
        tel.observe("lat", float(s))
    h = tel.histogram("lat")
    assert h.count == len(samples)
    assert h.sum == pytest.approx(samples.sum())
    for q in (0.5, 0.9, 0.99):
        true = float(np.quantile(samples, q))
        est = h.quantile(q)
        # upper-edge estimate: >= truth (up to rank rounding), within one
        # bucket ratio (~1.33) of it
        assert est >= true * 0.999
        assert est <= true * _H_GROWTH * 1.05
    assert h.quantile(1.0) == pytest.approx(samples.max())


def test_histogram_snapshot_record():
    tel = Telemetry(MemorySink())
    for v in (1e-5, 1e-5, 3.0):
        tel.observe("x", v)
    rec = tel.histogram("x").snapshot("x")
    validate(rec)
    assert rec["count"] == 3
    assert sum(c for _, c in rec["buckets"]) == 3
    # bucket upper edges bound their contents
    assert any(edge >= 3.0 for edge, _ in rec["buckets"])


# ------------------------------------------------------- sinks and schema

def test_filesink_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "tel.jsonl")
    tel = Telemetry(FileSink(path))
    with tel.span("work", n=3):
        tel.count("things", 2)
        tel.observe("lat", 0.01)
        tel.gauge("depth", 4)
    tel.flush()
    lines = open(path).read().splitlines()
    records = [json.loads(ln) for ln in lines]
    kinds = [r["kind"] for r in records]
    assert kinds == ["span", "counter", "gauge", "hist"]
    for r in records:
        validate(r)
    assert records[0]["attrs"] == {"n": 3}


def test_validate_rejects_malformed():
    with pytest.raises(ValueError):
        validate({"kind": "nope"})
    with pytest.raises(ValueError):
        validate({"kind": "span", "name": "a"})  # missing fields
    with pytest.raises(ValueError):
        validate({"kind": "counter", "name": "a", "value": "high"})
    with pytest.raises(ValueError):
        validate([])  # not a dict


def test_bench_record_matches_legacy_fields():
    rec = bench_record("bench/x", 12.345, "pct=1", ts=1700000000.123456,
                       rev="abc1234", backend="cpu", device_count=1)
    validate(rec)
    assert rec["kind"] == "bench"
    assert rec["us"] == 12.3  # round(value, 1), as the legacy writer did
    assert rec["ts"] == 1700000000.123
    assert rec["name"] == "bench/x" and rec["derived"] == "pct=1"
    assert rec["backend"] == "cpu" and rec["device_count"] == 1


def test_memory_sink_keeps_only_its_prefixes():
    tel = Telemetry()
    kept = tel.attach(MemorySink(prefixes=("train/",)))
    with tel.span("train/epoch"):
        with tel.span("hooks"):
            pass
    tel.count("train/steps")
    tel.flush()
    assert [r["name"] for r in kept.records] == ["train/epoch",
                                                 "train/steps"]


def test_attach_detach_tee():
    tel = Telemetry(MemorySink())
    extra = tel.attach(MemorySink())
    tel.count("a")
    tel.detach(extra)
    tel.flush()
    # the detached sink saw nothing (flush came after detach)
    assert extra.records == []
    assert tel.enabled


# ------------------------------------------------------- disabled overhead

def test_disabled_overhead_is_negligible():
    tel = Telemetry()
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tel.span("hot"):
            pass
        tel.count("c")
        tel.observe("h", 0.1)
    per_iter = (time.perf_counter() - t0) / n
    # one nullcontext + two early returns; generous bound for slow CI
    assert per_iter < 20e-6, f"disabled telemetry costs {per_iter:.2e}s/iter"


# ------------------------------------------- profiler trace and compiles

def _traced(fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace; the host events as
    ``{thread line index: [event names]}`` of the ``/host:CPU`` plane."""
    import glob
    import os
    import tempfile

    import jax
    from jax.profiler import ProfileData

    logdir = tempfile.mkdtemp(prefix="obs-trace-")
    jax.profiler.start_trace(logdir)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out[i] = [ev.name for ev in line.events]
    return out


def test_enabled_span_is_a_trace_event_on_its_thread():
    import threading

    tel = Telemetry()
    sink = tel.attach(MemorySink())

    def worker():
        with tel.span("obs/worker"):
            time.sleep(0.001)

    def body():
        with tel.span("obs/main", size=3):
            t = threading.Thread(target=worker)
            t.start()
            t.join()

    lines = _traced(body)
    main = [i for i, names in lines.items() if "obs/main" in names]
    other = [i for i, names in lines.items() if "obs/worker" in names]
    assert len(main) == 1 and len(other) == 1 and main != other
    # The event is named by the span's name alone; attrs stay in the record.
    assert not any("size" in n for names in lines.values() for n in names)
    assert [r["attrs"] for r in sink.records
            if r["name"] == "obs/main"] == [{"size": 3}]


def test_disabled_span_is_the_null_context_and_no_trace_event():
    tel = Telemetry()
    assert tel.span("obs/off") is tel.span("obs/other")  # cached

    def body():
        with tel.span("obs/off"):
            pass

    lines = _traced(body)
    assert not any("obs/off" in names for names in lines.values())


def test_hook_manager_spans_each_hook_inside_hooks():
    from repro.core.batch import Batch
    from repro.core.hooks import HookManager, LambdaHook

    def add(attr):
        def fn(b):
            b[attr] = b["src"] + 1
            return b
        return fn

    tel = Telemetry()
    sink = tel.attach(MemorySink())
    m = HookManager(telemetry=tel)
    m.register(LambdaHook(add("a"), produces=["a"], name="first"))
    m.register(LambdaHook(add("b"), requires=["a"], produces=["b"],
                          name="second"))
    batch = Batch({"src": np.arange(3), "dst": np.arange(3),
                   "time": np.arange(3)})
    m.execute(batch)
    m.execute(batch)
    paths = [r["path"] for r in sink.records]
    assert paths == ["hooks.hooks/first", "hooks.hooks/second", "hooks"] * 2
    # Without telemetry the manager runs the same hooks and records nothing.
    assert "b" in HookManager().register_all(m.hooks()).execute(batch).attrs


def test_compiles_counts_a_fresh_jit_and_not_a_cached_call():
    import jax
    import jax.numpy as jnp

    x, y = jnp.arange(7.0), jnp.arange(8.0)
    tel = Telemetry()
    sink = tel.attach(MemorySink())

    @jax.jit
    def fresh_fn(a):
        return a * 3.0 + 1.0

    fresh_fn(x).block_until_ready()
    assert tel.counter_value("jax/compiles") == 1
    (rec,) = [r for r in sink.records if r["name"] == "jax/compile"]
    assert rec["attrs"] == {"fun": "jit(fresh_fn)"} and rec["dur_s"] > 0
    validate(rec)
    fresh_fn(x).block_until_ready()  # cached: nothing new
    assert tel.counter_value("jax/compiles") == 1
    fresh_fn(y).block_until_ready()  # a new shape compiles
    assert tel.counter_value("jax/compiles") == 2


def test_compile_listener_is_registered_only_while_enabled():
    import jax

    from repro.obs.telemetry import _compiles

    tel = Telemetry()
    sink = MemorySink()
    tel.attach(sink)
    assert _compiles.registered
    tel.detach(sink)
    assert not _compiles.registered

    @jax.jit
    def unseen(a):
        return a - 2.0

    unseen(np.ones(5, np.float32))
    tel.attach(sink)
    assert tel.counter_value("jax/compiles") == 0  # compiled while off
    tel.detach(sink)


def test_prefetch_loader_wait_span_per_batch():
    from repro.core.loader import PrefetchLoader

    tel = Telemetry()
    sink = tel.attach(MemorySink())
    batches = [{"x": np.arange(4) + i} for i in range(3)]
    got = list(PrefetchLoader(batches, telemetry=tel))
    assert len(got) == 3
    tel.flush()
    names = [r["name"] for r in sink.records]
    # One wait per batch plus the one that meets the end of the stream.
    assert names.count("loader/wait") == 4
    assert names.count("loader/stage") == 3
    assert tel.histogram("loader/prefetch_wait") is None
    assert all(r["dur_s"] >= 0 for r in sink.records
               if r["name"] == "loader/wait")


def test_pipeline_hooks_share_its_telemetry_in_train_and_eval():
    from repro.core.tg_hooks import DeviceRecencyNeighborHook
    from repro.data import generate
    from repro.train.loop import CTDGLinkPipeline

    tel = Telemetry()
    sink = tel.attach(MemorySink())
    pipe = CTDGLinkPipeline("tgat", generate("wikipedia", scale=0.003, seed=0),
                            batch_size=100, seed=0, device_sampling=True,
                            telemetry=tel)
    assert pipe.manager.telemetry is tel
    hook = "hooks/" + next(h.name for h in pipe.manager.hooks()
                           if isinstance(h, DeviceRecencyNeighborHook))
    pipe.train_epoch()
    train = [r["name"] for r in sink.drain()]
    pipe.evaluate("val")
    evals = [r["name"] for r in sink.records]
    n_train = -(-len(pipe.train_data.src) // 100)
    assert train.count("hooks") == train.count(hook) == n_train
    assert train.count("ctdg/dispatch") == n_train
    assert train.count("loader/stage") == n_train
    assert train.count("loader/wait") == n_train + 1
    assert "ctdg/step" not in train
    assert evals.count("ctdg/eval_dispatch") == -(-len(pipe.val_data.src)
                                                  // 100)
    assert evals.count(hook) > evals.count("ctdg/eval_dispatch")  # + warm


def test_tgn_second_epoch_compiles_nothing():
    """A new epoch's fresh memory is placed as trained memory is, so its
    first step reuses the compiled step (placed apart, it compiled a
    second one)."""
    from repro.data import generate
    from repro.train.loop import CTDGLinkPipeline

    # Fixed-width batches: no node-event tensors of varying length.
    data = generate("wikipedia", scale=0.003, seed=0)
    tel = Telemetry()
    sink = tel.attach(MemorySink())
    pipe = CTDGLinkPipeline("tgn", data, batch_size=64, seed=0,
                            device_sampling=True, telemetry=tel)
    pipe.train_epoch()
    assert any(r["name"] == "jax/compile"
               and r["attrs"]["fun"] == "jit(train_step)"
               for r in sink.records)
    tel.reset()
    sink.drain()
    pipe.train_epoch()
    assert tel.counter_value("jax/compiles") == 0
    assert not [r for r in sink.records if r["name"] == "jax/compile"]


# --------------------------------------------------- train-history parity

class _ScriptedPipeline:
    """Duck-typed pipeline returning scripted values (no JAX involved)."""

    def __init__(self):
        self.losses = [(0.9, 1.5), (0.7, 1.4), (0.5, 1.3)]
        self.metrics = iter([0.11, 0.22])
        self.saved = 0

    def train_epoch(self):
        return self.losses.pop(0)

    def evaluate(self, split):
        return next(self.metrics), 0.01

    def save_checkpoint(self, ckpt_dir, step):
        self.saved += 1
        return f"{ckpt_dir}/ckpt_{step}"


def test_trainloop_history_from_records_parity(tmp_path):
    from repro.train.loop import TrainLoop, history_from_records

    tel = Telemetry()
    sink = tel.attach(MemorySink())
    loop = TrainLoop(_ScriptedPipeline(), telemetry=tel)
    history = loop.fit(epochs=3, eval_every=2, eval_split="val",
                       ckpt_dir=str(tmp_path), ckpt_every=3)
    expected = {
        "loss": [0.9, 0.7, 0.5],
        "train_secs": [1.5, 1.4, 1.3],
        "eval": [(1, 0.11)],
        "ckpts": [f"{tmp_path}/ckpt_2"],
    }
    assert history == expected  # identical keys AND values
    # and the records alone rebuild the same history
    assert history_from_records(sink.records) == expected
    for r in sink.records:
        validate(r)


# -------------------------------------------------- end-to-end acceptance

def test_single_sink_observes_train_serve_and_storage(tmp_path):
    """ISSUE acceptance: one ``repro.obs`` sink sees a CTDG link epoch, a
    serving chaos run, and a windowed out-of-core storage epoch (plus a
    streaming-CSR build), and every emitted record validates."""
    from repro.core import DGData
    from repro.core.loader import PrefetchLoader
    from repro.serve import FaultInjector, OnlineGraphService
    from repro.storage import InMemoryStore, StoreEventLoader, streaming_csr
    from repro.train.loop import CTDGLinkPipeline, TrainLoop

    path = str(tmp_path / "run.jsonl")
    tel = Telemetry(FileSink(path))
    mem = tel.attach(MemorySink())

    # -- one CTDG link epoch through TrainLoop --------------------------
    from repro.data import generate

    data = generate("tiny").slice_events(0, 300)
    pipe = CTDGLinkPipeline("tgat", data, batch_size=100, seed=0,
                            telemetry=tel)
    TrainLoop(pipe).fit(epochs=1)
    assert any(r["kind"] == "span" and r["name"] == "ctdg/epoch"
               for r in mem.records)
    assert any(r["kind"] == "span" and r["name"] == "ctdg/dispatch"
               for r in mem.records)

    # -- one serving chaos burst ----------------------------------------
    inj = FaultInjector(seed=0, dup_p=0.1, fail_p=0.3)
    rng = np.random.default_rng(1)
    events = [(int(rng.integers(40)), int(rng.integers(40)), 100 + i, i)
              for i in range(80)]
    with OnlineGraphService(40, k=4, flush_interval=0.002,
                            fault_injector=inj, telemetry=tel) as svc:
        svc.ingest_many(inj.perturb_events(events))
        svc.drain()
        rs = [svc.submit_link(i % 40, (i * 3 + 1) % 40, 500).result(30)
              for i in range(10)]
    assert all(r.status is not None for r in rs)
    assert tel.counter_value("serve/events_applied") > 0

    # -- one windowed storage epoch + streaming CSR ---------------------
    src = rng.integers(0, 40, 400)
    dst = rng.integers(0, 40, 400)
    t = np.sort(rng.integers(0, 5000, 400))
    store = InMemoryStore.from_data(
        DGData.from_arrays(src, dst, t, granularity="s"))
    loader = PrefetchLoader(
        StoreEventLoader(store, batch_size=100, telemetry=tel),
        telemetry=tel)
    assert len(list(loader)) == 4
    streaming_csr(store, chunk_size=150, telemetry=tel)
    assert tel.counter_value("storage/windows_read") > 0
    assert tel.counter_value("storage/csr_windows") > 0
    assert tel.counter_value("loader/batches") == 4

    # -- every record in the shared JSONL file validates ----------------
    tel.flush()
    records = [json.loads(ln) for ln in open(path).read().splitlines()]
    assert len(records) == len(mem.records)
    for r in records:
        validate(r)
    names = {r["name"] for r in records}
    # all three subsystems landed in ONE file
    assert "ctdg/epoch" in names
    assert "storage/csr_pass1" in names
    assert any(n.startswith("serve/") for n in names)
    # and the report renders without blowing up
    assert "section" in span_report(records, min_pct=0.0)
    assert "|" in span_report(records, min_pct=0.0, markdown=True)
