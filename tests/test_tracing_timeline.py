"""One timeline: `obs.tracing.span` on the profiler's clock, the seams of
`Trainer.fit`, a row per drain of it with the collector's pauses and a
`stall` event, the named scopes on the step's operations and the compile
counters that `enable_compile_cache` feeds."""

import gc
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.obs import tracing
from deeplearning_cfn_tpu.obs.profiler import StepProfiler
from deeplearning_cfn_tpu.obs.recorder import FlightRecorder
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.data import Batch
from deeplearning_cfn_tpu.train.trainer import Trainer, TrainerConfig


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.reset_aggregates()
    yield
    tracing.reset_aggregates()


# --- the primitive ----------------------------------------------------------


def test_span_lands_on_the_host_plane_in_the_time_base_of_the_operations(tmp_path):
    from jax.profiler import ProfileData

    rec = FlightRecorder()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with tracing.span("fit.step", rec, journal=False, step_num=7):
        with tracing.span("fit.dispatch", rec, journal=False, shard="a", n=3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(str(path)).planes if p.name == "/host:CPU")
    events = [(line.name, e) for line in host.lines for e in line.events]
    (step,) = [e for _, e in events if e.name == "fit.step"]
    (dispatch,) = [e for _, e in events if e.name == "fit.dispatch"]
    assert dict(step.stats)["step_num"] == 7
    assert {k: v for k, v in dispatch.stats if k in ("shard", "n")} == {"shard": "a", "n": 3}
    assert step.start_ns <= dispatch.start_ns
    assert dispatch.start_ns + dispatch.duration_ns <= step.start_ns + step.duration_ns
    # The jitted call's own operations, on the runtime's threads of the same
    # plane, lie inside the span that waited for them.
    ops = [e for _, e in events if dict(e.stats).get("hlo_module", "").startswith("jit_")]
    assert ops
    for e in ops:
        assert dispatch.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= dispatch.start_ns + dispatch.duration_ns
    # Folded and kept, but not journalled.
    assert tracing.span_aggregates()["fit.dispatch"]["count"] == 1
    assert [r[1] for r in tracing.recent_spans()] == ["fit.dispatch", "fit.step"]
    assert rec.tail() == []


def test_span_journals_under_the_name_it_is_given_and_keeps_its_wall_clock_start(monkeypatch):
    rec = FlightRecorder()
    clock = {"t": 5.0}
    monkeypatch.setattr(tracing, "_perf_counter", lambda: clock["t"])
    monkeypatch.setattr(tracing, "_time_ns", lambda: int(clock["t"] * 1e9))
    with tracing.span("fit.step", rec, journal="train_step", step_num=3):
        clock["t"] += 0.25
    with tracing.span("fit.checkpoint", rec, journal="checkpoint", step=3):
        clock["t"] += 0.5
    assert [(e["span"], e["seconds"]) for e in rec.tail()] == [("train_step", 0.25), ("checkpoint", 0.5)]
    assert "step_num" not in rec.tail()[0] and rec.tail()[1]["step"] == 3
    assert set(tracing.span_aggregates()) == {"fit.step", "fit.checkpoint"}
    me = threading.get_ident()
    assert tracing.recent_spans() == [
        [me, "fit.step", 5_000_000_000, 250_000_000],
        [me, "fit.checkpoint", 5_250_000_000, 500_000_000],
    ]


def test_recent_spans_are_bounded():
    for _ in range(tracing.RECENT_SPANS + 10):
        with tracing.span("s", journal=False):
            pass
    assert len(tracing.recent_spans()) == tracing.RECENT_SPANS
    assert tracing.span_aggregates()["s"]["count"] == tracing.RECENT_SPANS + 10


def test_counters_fold_counts_and_totals_and_freeze_apart():
    tracing.counter("compile.trace_s", 0.5)
    tracing.counter("compile.trace_s", 0.25)
    tracing.counter("compile.cache_hit")
    tracing.counter("other", 2.0)
    tracing.freeze_counters("compile.", "first_step.")
    tracing.counter("compile.trace_s", 4.0)
    got = tracing.counters()
    assert got["compile.trace_s"] == {"count": 3, "total": 4.75}
    assert got["first_step.compile.trace_s"] == {"count": 2, "total": 0.75}
    assert got["first_step.compile.cache_hit"] == {"count": 1, "total": 1.0}
    assert "first_step.other" not in got
    # A later freeze replaces the copy.
    tracing.freeze_counters("compile.", "first_step.")
    assert tracing.counters()["first_step.compile.trace_s"] == {"count": 3, "total": 4.75}


def test_obs_imports_and_spans_without_jax():
    code = (
        "import sys\n"
        "import deeplearning_cfn_tpu.obs as obs\n"
        "assert 'jax' not in sys.modules\n"
        "with obs.span('s', journal=False, step_num=1, a=2):\n"
        "    pass\n"
        "obs.counter('c', 2.0)\n"
        "assert 'jax' not in sys.modules\n"
        "assert obs.span_aggregates()['s']['count'] == 1 and obs.counters()['c']['total'] == 2.0\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# --- the seams of fit -------------------------------------------------------


class _Hooks:
    """A source, a logger, a checkpointer and a stop function that each
    move a virtual clock: time passes nowhere else."""

    def __init__(self, clock, batch, save_at=()):
        self.clock, self.batch, self.save_at = clock, batch, set(save_at)
        self.saved = []

    def batches(self, n):
        for _ in range(n):
            self.clock["t"] += 0.001
            yield self.batch

    def step(self, step, loss):  # the logger
        self.clock["t"] += 0.002

    def should_save(self, step):
        self.clock["t"] += 0.0005
        return step in self.save_at

    def save(self, step, state):
        self.clock["t"] += 0.004
        self.saved.append(step)

    def stop(self, metrics):
        self.clock["t"] += 0.003
        return False


def _lenet_trainer(log_every=2):
    from deeplearning_cfn_tpu.models.lenet import LeNet

    mesh = build_mesh(MeshSpec.data_parallel(2), jax.devices()[:2])
    trainer = Trainer(LeNet(num_classes=4), mesh, TrainerConfig(log_every=log_every))
    x = np.zeros((8, 8, 8, 1), np.float32)
    y = np.zeros((8,), np.int32)
    state = trainer.init(jax.random.key(0), jnp.asarray(x))
    return trainer, state, Batch(x, y)


@pytest.mark.parametrize("steps_per_call, steps", [(1, 5), (2, 5)])
def test_every_hook_of_the_loop_lies_under_exactly_one_seam(monkeypatch, steps_per_call, steps):
    trainer, state, batch = _lenet_trainer()
    clock = {"t": 100.0}
    hooks = _Hooks(clock, batch, save_at={2, 4})
    rec = FlightRecorder()
    monkeypatch.setattr(tracing, "get_recorder", lambda: rec)
    monkeypatch.setattr(tracing, "_perf_counter", lambda: clock["t"])
    monkeypatch.setattr(tracing, "_time_ns", lambda: int(round(clock["t"] * 1e9)))
    t0 = clock["t"]
    trainer.fit(
        state, hooks.batches(steps), steps=steps, logger=hooks, checkpointer=hooks,
        stop_fn=hooks.stop, prefetch=0, steps_per_call=steps_per_call,
    )
    rows = [r for r in tracing.recent_spans() if r[1].startswith("fit.")]
    assert {r[0] for r in rows} == {threading.get_ident()}
    leaves = [r for r in rows if r[1] != "fit.step"]
    # Time passed only inside the hooks; every nanosecond of it lies under
    # one leaf seam, none under two and none between them.
    # (to the nanosecond each span's float seconds round to)
    assert abs(sum(r[3] for r in leaves) - round((clock["t"] - t0) * 1e9)) <= len(leaves)
    spans = sorted((r[2], r[2] + r[3]) for r in leaves)
    assert all(a_end <= b_start + 1 for (_, a_end), (b_start, _) in zip(spans, spans[1:]))
    names = {r[1] for r in rows}
    assert names == {
        "fit.step", "fit.data_wait", "fit.h2d", "fit.dispatch", "fit.sync", "fit.log",
        "fit.checkpoint",
    }
    by_name = {n: sum(r[3] for r in leaves if r[1] == n) for n in names}
    assert abs(by_name["fit.checkpoint"] - len(hooks.saved) * 4_000_000) <= len(hooks.saved)
    assert by_name["fit.h2d"] == by_name["fit.dispatch"] == by_name["fit.sync"] == 0
    # One journalled line per dispatch under the old name, one per save.
    journalled = [e["span"] for e in rec.tail() if e["kind"] == "span"]
    dispatches = tracing.span_aggregates()["fit.dispatch"]["count"]
    assert journalled.count("train_step") == dispatches == tracing.span_aggregates()["fit.step"]["count"]
    assert journalled.count("checkpoint") == len(hooks.saved) > 0
    assert set(journalled) == {"train_step", "checkpoint"}


@pytest.mark.parametrize("steps_per_call", [1, 2])
def test_a_step_profiler_still_receives_its_phases(steps_per_call):
    trainer, state, batch = _lenet_trainer()
    prof = StepProfiler(name="t")
    trainer.fit(
        state, iter([batch] * 6), steps=6, profiler=prof, steps_per_call=steps_per_call
    )
    snap = prof.snapshot()
    assert snap["steps"] == 6
    for phase in ("data_wait", "h2d", "dispatch", "compute", "host"):
        assert snap["phases"][phase]["count"] > 0, phase
    # compute is amortised over the steps a sync drained
    assert snap["phases"]["compute"]["count"] >= 6
    assert trainer.first_step_seconds > 0 and trainer.first_step_at > 0


# --- a row per drain, the collector's pauses, the stall event -----------------


@pytest.mark.parametrize("steps_per_call, steps", [(1, 5), (2, 5)])
def test_every_drain_of_the_loop_leaves_a_row_as_the_clock_dictates(monkeypatch, steps_per_call, steps):
    trainer, state, batch = _lenet_trainer()
    clock = {"t": 100.0}
    hooks = _Hooks(clock, batch, save_at={3})
    monkeypatch.setattr(tracing, "get_recorder", lambda: FlightRecorder())
    monkeypatch.setattr(tracing, "_perf_counter", lambda: clock["t"])
    monkeypatch.setattr(tracing, "_time_ns", lambda: int(round(clock["t"] * 1e9)))
    trainer.fit(
        state, hooks.batches(steps), steps=steps, logger=hooks, checkpointer=hooks,
        stop_fn=hooks.stop, prefetch=0, steps_per_call=steps_per_call,
    )
    spans = tracing.recent_spans()
    syncs = [r[2] + r[3] for r in spans if r[1] == "fit.sync"]
    dispatches = [r[2] + r[3] for r in spans if r[1] == "fit.dispatch"]
    rows = tracing.recent_drains()
    # One row for every wait that drained, none for the first step's.
    assert [r["sync_end_ns"] for r in rows] == syncs[1:]
    assert sum(r["steps"] for r in rows) == steps and rows[-1]["step"] == steps
    assert [r["step"] for r in rows] == list(np.cumsum([r["steps"] for r in rows]))
    assert {r["thread"] for r in rows} == {threading.get_ident()}
    for before, row in zip([None] + rows, rows):
        # the bridge: one return on both clocks
        assert row["sync_end_ns"] == int(round(row["sync_end_s"] * 1e9))
        if before is None:
            assert row["interval_s"] is None and row["gc_s"] is None and row["nivcsw"] is None
        else:
            assert row["interval_s"] == pytest.approx(row["sync_end_s"] - before["sync_end_s"], abs=1e-9)
            assert row["interval_s"] > 0 and row["gc_s"] >= 0
            assert row["nivcsw"] >= 0 and row["majflt"] >= 0  # Linux keeps them by thread
        following = [d for d in dispatches if d > row["sync_end_ns"]]
        if following:
            # from the return to the end of the next dispatch: stop_fn's 3 ms,
            # then whatever the source took, and nothing on the device
            assert row["exposed_s"] == pytest.approx((following[0] - row["sync_end_ns"]) / 1e9, abs=1e-9)
            assert row["exposed_s"] >= 0.004 - 1e-9
            assert row["exposed_gc_s"] >= 0
        else:
            assert row["exposed_s"] is None and row["exposed_gc_s"] is None
    assert rows[-1]["exposed_s"] is None and rows[0]["exposed_s"] is not None


def test_the_collectors_hook_folds_every_pause_and_keeps_the_long_ones_as_spans(monkeypatch):
    clock = {"t": 50.0}
    monkeypatch.setattr(tracing, "_perf_counter", lambda: clock["t"])
    monkeypatch.setattr(tracing, "_time_ns", lambda: int(round(clock["t"] * 1e9)))
    gc.disable()  # no collection of the interpreter's own between the calls below
    try:
        for generation, seconds in ((0, 0.0004), (2, 0.003), (1, 0.0009), (2, 0.25)):
            tracing._on_gc("start", {"generation": generation})
            clock["t"] += seconds
            tracing._on_gc("stop", {"generation": generation, "collected": 0})
            clock["t"] += 1.0
        tracing._on_gc("stop", {"generation": 2})  # a stop with no start: installed mid-collection
    finally:
        gc.enable()
    got = tracing.counters()
    assert got["gc.pause_s"] == {"count": 4, "total": pytest.approx(0.2543)}
    me = threading.get_ident()
    assert tracing.recent_spans() == [
        [me, "host.gc", 51_000_400_000, 3_000_000],
        [me, "host.gc", 53_004_300_000, 250_000_000],
    ]
    tracing.reset_aggregates()
    assert "gc.pause_s" not in tracing.counters()


def test_the_hook_is_installed_once_however_many_loops_are_built():
    from deeplearning_cfn_tpu.train.trainer import _FitSeams

    trainer, _, _ = _lenet_trainer()
    for _ in range(3):
        _FitSeams(trainer, None)
        tracing.Drains()
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = tracing.counters().get("gc.pause_s", {"count": 0})["count"]
    gc.collect()
    after = tracing.counters()["gc.pause_s"]
    assert after["count"] >= before + 1 and after["total"] > 0


def test_obs_keeps_drains_without_jax_and_hooks_the_collector_only_when_a_loop_is_built():
    code = (
        "import gc, sys\n"
        "import deeplearning_cfn_tpu.obs as obs\n"
        "from deeplearning_cfn_tpu.obs import tracing\n"
        "assert tracing._on_gc not in gc.callbacks\n"
        "drains = tracing.Drains()\n"
        "assert gc.callbacks.count(tracing._on_gc) == 1\n"
        "drains.returned(2, 2); drains.dispatched(); drains.returned(4, 2)\n"
        "first, second = obs.recent_drains()\n"
        "assert first['exposed_s'] >= 0 and second['interval_s'] > 0 and second['exposed_s'] is None\n"
        "assert 'jax' not in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize(
    "usual_s, slow_s, saves, fires",
    [
        (0.5, 1.3, False, True),  # 2.6 times the median and 0.4 s a step over it
        (0.5, 1.3, True, True),  # the same with a save in it: a stall, and it says during what
        (0.01, 0.05, False, False),  # five times the median, 40 ms over it: under the floor
        (0.5, 0.6, False, False),  # 100 ms over it, a fifth: under the factor
    ],
)
def test_a_stall_is_journalled_once_for_a_drain_over_both_thresholds(monkeypatch, usual_s, slow_s, saves, fires):
    import types

    from deeplearning_cfn_tpu.train import trainer as trainer_module

    clock = {"t": 10.0}
    monkeypatch.setattr(tracing, "_perf_counter", lambda: clock["t"])
    monkeypatch.setattr(tracing, "_time_ns", lambda: int(round(clock["t"] * 1e9)))
    rec = FlightRecorder()
    monkeypatch.setattr(tracing, "get_recorder", lambda: rec)
    monkeypatch.setattr(trainer_module, "get_recorder", lambda: rec)
    seams = trainer_module._FitSeams(types.SimpleNamespace(), None)
    step = 0
    for n in range(24):
        slow = n == 16
        # the host's segment: 1 ms of fit.log, or the whole surplus before the slow drain
        with seams("fit.log"):
            clock["t"] += 0.001 + (2 * (slow_s - usual_s) if slow else 0.0)
        with seams("fit.dispatch"):
            clock["t"] += 0.0005
        seams.dispatched()
        if slow and saves:
            with seams.checkpoint(step):
                pass
        clock["t"] += 2 * usual_s - 0.0015
        step += 2
        with seams.drain(step, 2):
            pass
    events = [e for e in rec.tail() if e["kind"] == "stall"]
    assert len(events) == (1 if fires else 0)
    if fires:
        (event,) = events
        assert event["step"] == 34 and event["steps"] == 2
        assert event["interval_s"] == pytest.approx(2 * slow_s)
        assert event["median_step_s"] == pytest.approx(usual_s)
        assert event["excess_s"] == pytest.approx(2 * (slow_s - usual_s))
        # the segment inside the slow interval is the one the drain before opened
        assert event["exposed_s"] == pytest.approx(0.0015 + 2 * (slow_s - usual_s))
        assert event["seam"] == "fit.log"
        assert event["during"] == (["fit.checkpoint"] if saves else None)
        assert {"gc_s", "nivcsw", "majflt", "exposed_gc_s", "sync_end_ns"} <= set(event)


def test_a_row_counts_no_negative_pause_when_the_totals_were_reset_under_a_live_loop():
    drains = tracing.Drains()
    tracing._gc_totals[:] = [3, 0.5]
    drains.returned(1, 1)
    tracing.reset_aggregates()  # zeroes the collector's totals; the loop keeps its snapshot
    drains.dispatched()
    row = drains.returned(2, 1)
    assert row["gc_s"] == 0.0 and drains.last is row
    assert tracing.recent_drains()[0]["gc_s"] == 0.0


def test_recent_drains_are_bounded_and_cleared_with_the_aggregates():
    drains = tracing.Drains()
    for n in range(tracing.RECENT_DRAINS + 10):
        drains.returned(n + 1, 1)
    rows = tracing.recent_drains()
    assert len(rows) == tracing.RECENT_DRAINS and rows[-1]["step"] == tracing.RECENT_DRAINS + 10
    assert drains.returned(0, 0) is None  # a wait that drained nothing is no drain
    assert len(tracing.recent_drains()) == tracing.RECENT_DRAINS
    rows[0]["step"] = -1  # a copy: the program's rows are its own
    assert tracing.recent_drains()[0]["step"] != -1
    tracing.reset_aggregates()
    assert tracing.recent_drains() == []


# --- the compile counters ---------------------------------------------------


def test_first_step_counters_hold_what_was_compiled_before_the_first_step():
    from deeplearning_cfn_tpu.utils import compile_cache

    compile_cache.count_compiles()
    trainer, state, batch = _lenet_trainer()
    trainer.compile_stats(state, batch.x, batch.y)
    trainer.fit(state, iter([batch] * 3), steps=3)
    frozen = {k: v for k, v in tracing.counters().items() if k.startswith("first_step.")}
    assert frozen["first_step.compile.trace_s"]["count"] >= 1
    assert frozen["first_step.compile.lower_s"]["count"] >= 1
    assert frozen["first_step.compile.backend_s"]["count"] >= 1
    assert all(v["total"] >= 0 for v in frozen.values())
    before = tracing.counters()["compile.backend_s"]["count"]
    jax.jit(lambda a: a * 3 + 1)(jnp.ones((3,))).block_until_ready()
    after = tracing.counters()
    assert after["compile.backend_s"]["count"] > before
    assert {k: v for k, v in after.items() if k.startswith("first_step.")} == frozen
    # the once-per-run spans are journalled ones
    assert tracing.span_aggregates()["trainer.init"]["count"] == 1
    assert tracing.span_aggregates()["trainer.compile_stats"]["count"] == 1


# --- names on the device's time ---------------------------------------------


def _hlo_text(lowered):
    """The lowered program's HLO module as text: the serialized proto, which
    holds every operation's `op_name` whole (the printed forms leave the
    metadata out or split it over locations)."""
    return lowered.compiler_ir(dialect="hlo").as_serialized_hlo_module_proto().decode("latin-1")


def _has(text: str, scope: str) -> bool:
    """The scope on some operation's name stack, by the benchmark's own
    rule: a component of its own or inside a transformation's wrapper."""
    from benchmarks.scope_reduce import has_scope

    return has_scope(text, scope)


def _lowered_text(trainer, state, x, y):
    with jax.set_mesh(trainer.mesh):
        return _hlo_text(trainer.step_fn.lower(state, x, y))


@pytest.mark.parametrize("accum", [1, 2])
def test_the_lowered_resnet_step_carries_the_steps_scopes(accum):
    from deeplearning_cfn_tpu.models.resnet import ResNet

    mesh = build_mesh(MeshSpec.data_parallel(2), jax.devices()[:2])
    model = ResNet(stage_sizes=(1, 1), num_classes=4, num_filters=8)
    config = TrainerConfig(
        grad_accum_steps=accum, has_train_arg=True, input_stats=((0.5,) * 3, (0.25,) * 3)
    )
    trainer = Trainer(model, mesh, config)
    x = np.zeros((4, 32, 32, 3), np.uint8)
    y = np.zeros((4,), np.int32)
    state = trainer.init(jax.random.key(0), jnp.asarray(x))
    text = _lowered_text(trainer, state, x, y)
    assert "jit(train_step)" in text
    for scope in ("input", "loss", "optimizer", "transpose", "stem", "stage1_block1", "head", "xent"):
        assert _has(text, scope), scope
    assert "/loss/" in text and "/optimizer/" in text and "/input/" in text


@pytest.mark.parametrize("accum", [1, 2])
def test_the_lowered_decoder_step_carries_the_steps_and_the_blocks_scopes(accum):
    import dataclasses

    from deeplearning_cfn_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), remat=True)
    mesh = build_mesh(MeshSpec.data_parallel(2), jax.devices()[:2])
    trainer = llama.make_trainer(cfg, mesh, TrainerConfig(grad_accum_steps=accum))
    tokens = np.zeros((4, 32), np.int32)
    state = trainer.init(jax.random.key(0), jnp.asarray(tokens))
    text = _lowered_text(trainer, state, tokens, tokens)
    for scope in (
        "loss", "optimizer", "transpose", "rematted_computation", "embed", "attn_norm",
        "attn", "qkv", "rope", "core", "out", "mlp_norm", "mlp", "final_norm", "head", "xent",
    ):
        assert _has(text, scope), scope
    assert "/attn/qkv/" in text and "/loss/" in text and "/optimizer/" in text


def _lower_decode_or_serve_program(program: str):
    """The decode and serve programs that run `llama.decoder_block`, each
    lowered at a toy size."""
    from deeplearning_cfn_tpu.models import llama, llama_decode
    from deeplearning_cfn_tpu.serve import engine
    from deeplearning_cfn_tpu.serve.paged_cache import init_paged_cache

    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    key = jax.random.key(1)
    prompt = jnp.zeros((1, 8), jnp.int32)
    length = jnp.asarray(5, jnp.int32)
    if program == "_forward_cached":
        cache = llama_decode.init_cache(cfg, 1, 16)
        return jax.jit(llama_decode._forward_cached, static_argnums=0).lower(
            cfg, params, prompt, cache, jnp.asarray(0, jnp.int32)
        )
    if program == "prefill_kv":
        return engine.prefill_kv.lower(cfg, params, prompt, length, key)
    pool = init_paged_cache(cfg, 8, 4)
    if program == "paged_prefill":
        return engine.paged_prefill.lower(
            cfg, params, pool, prompt, length, jnp.arange(4, dtype=jnp.int32), key
        )
    assert program == "paged_decode_step"
    return engine.paged_decode_step.lower(
        cfg, params, pool, jnp.zeros((2,), jnp.int32), jnp.asarray([5, 3], jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.asarray([True, False]), key,
    )


@pytest.mark.parametrize(
    "program", ["_forward_cached", "paged_prefill", "paged_decode_step", "prefill_kv"]
)
def test_the_decode_and_serve_programs_carry_the_blocks_scopes(program):
    """They run the trainer's block and tail, so a trace of the cached
    decoder or of the engine names what a trace of the trainer names."""
    text = _hlo_text(_lower_decode_or_serve_program(program))
    for scope in (
        "attn_norm", "attn", "qkv", "rope", "core", "out", "mlp_norm", "mlp", "final_norm", "head",
    ):
        assert _has(text, scope), scope
    assert "attn/qkv/" in text and "attn/core/" in text


@pytest.mark.parametrize("accum", [1, 2])
def test_the_overlapped_gradient_path_carries_the_steps_scopes(accum):
    """The comms-overlap engine takes stateless models with at most one
    sharded dimension a parameter: a small MLP, as its own tests use."""
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(32)(x.reshape((x.shape[0], -1)))))

    mesh = build_mesh(MeshSpec.data_parallel(2), jax.devices()[:2])
    config = TrainerConfig(
        strategy="dp", optimizer="sgd", grad_accum_steps=accum, comms_overlap=True,
        input_stats=((0.5,), (0.25,)),
    )
    trainer = Trainer(MLP(), mesh, config)
    x = np.zeros((8, 8, 8, 1), np.uint8)
    y = np.zeros((8,), np.int32)
    state = trainer.init(jax.random.key(0), jnp.asarray(x))
    text = _lowered_text(trainer, state, x, y)
    for scope in ("input", "loss", "optimizer", "transpose", "xent"):
        assert _has(text, scope), scope
    assert "/loss/" in text and "/optimizer/" in text and "/input/" in text


def test_the_flash_kernel_and_its_backward_are_named():
    from deeplearning_cfn_tpu.ops.pallas_attention import flash_attention

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=True)
        return out.astype(jnp.float32).sum()

    q = jnp.ones((1, 128, 2, 64), jnp.bfloat16)
    text = _hlo_text(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q))
    assert _has(text, "attn_bwd")
    assert "_flash_forward" in text


def test_the_fused_dense_kernels_are_named():
    from deeplearning_cfn_tpu.ops.pallas_fused import fused_dense

    x = jnp.ones((16, 128), jnp.bfloat16)
    w = jnp.ones((128, 128), jnp.bfloat16)
    b = jnp.zeros((128,), jnp.bfloat16)
    text = _hlo_text(jax.jit(lambda x, w, b: fused_dense(x, w, b, interpret=True)).lower(x, w, b))
    assert "fused_dense" in text
