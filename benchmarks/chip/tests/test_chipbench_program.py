"""The program's side of a traced run (``chipbench.programtrace``): its
spans and events on the trace's clock, the step's phases from the
profile's HLO, the four readers built on them, and the checks a traced run
prints."""
import os
import sys
import time
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import programtrace, tracefile  # noqa: E402
from chipbench.harness import load_module  # noqa: E402
from chipbench.programtrace import Program  # noqa: E402
from chipbench.tracefile import Trace  # noqa: E402

LOSS = "jit(train_step)/loss/jvp()/dot_general"
OPT = "jit(train_step)/optimizer/mul"
READERS = ("sync_idle_ms.train", "commit_wait_ms.train", "optimizer_ms.train",
           "compile_s.train")


def traced() -> Program:
    """Two steps of a traced run: the window is [5, 200] ns on the main
    thread, the program's anchor before it; its events already mapped."""
    main = [("txtrace.anchor", 1, 3), ("window", 5, 200),
            ("train", 5, 100), ("train.batch", 5, 8), ("train.dispatch", 8, 12),
            ("step", 9, 11), ("train.loss_sync", 12, 60), ("loss_sync", 13, 59),
            ("train.commit", 60, 90), ("commit", 61, 89),
            ("train", 100, 195), ("train.batch", 100, 104),
            ("train.dispatch", 104, 108), ("train.loss_sync", 108, 160),
            ("train.commit", 160, 190)]
    tr = Trace(
        ops={"/device:TPU:0": [
            ("%fusion.1 = f32[4] fusion(%p)", 10, 40),
            ("while.2", 20, 30),           # nested: the outer op's phase
            ("fusion.3", 40, 50), ("copy.4", 45, 55),
            ("%fusion.1 = f32[4] fusion(%p)", 110, 140),
            ("fusion.3", 140, 150)]},
        modules={"/device:TPU:0": [("jit_train_step", 10, 55),
                                   ("jit_train_step", 110, 150)]},
        spans={"/host:CPU/main": main})
    return Program(
        tr, (5, 200), mapped=True, compile_s=12.5,
        scopes={"fusion.1": LOSS, "while.2": OPT, "fusion.3": OPT,
                "copy.4": "jit(train_step)/copy"},
        events=[("train", 5, 100, 0, 0, ""), ("train", 100, 195, 0, 1, ""),
                ("train.commit", 59, 91, 0, 0, ""),
                ("train.commit", 159, 191, 0, 1, ""),
                ("txn", 61, 89, 0, -1, "#7"), ("commit", 65, 78, 0, -1, "#7"),
                ("vwait", 66, 70, 0, 3, ""),
                ("txn", 161, 189, 0, -1, "#9"), ("vwait", 170, 180, 0, 4, ""),
                ("lw_apply", 162, 165, 1, 4, "#9"),     # an executor's ring
                ("vwait", 60, 90, 2, 5, "")])           # the evaluator's


def read_all(p: Program, steps: int = 2) -> dict:
    """The four readers over a run whose program side is ``p``."""
    key = f"test-{id(p)}"
    programtrace._read[key] = p
    run = {"session": SimpleNamespace(trace_dir=key), "steps": steps}
    try:
        return {m: load_module("metrics", m).read(run) for m in READERS}
    finally:
        del programtrace._read[key]


def test_the_phases_sum_to_busy_time():
    p = traced()
    ph = programtrace.phases(p)
    assert ph == pytest.approx({"loss": 60, "optimizer": 20, "other": 5})
    busy = tracefile.reduce(p.trace)["busy_s"]
    assert sum(ph.values()) * 1e-9 == pytest.approx(busy)
    p.scopes = {}                 # no op names: every op is "other"
    assert programtrace.phases(p)["other"] * 1e-9 == pytest.approx(busy)
    assert [programtrace.phase_of(s) for s in (LOSS, OPT, None, "a/lossy/b")
            ] == ["loss", "optimizer", "other", "other"]
    assert programtrace.short_name("%fusion.250 = f32[8] fusion(%a)") == \
        "fusion.250"


def test_the_four_readers_give_their_hand_computed_values():
    assert read_all(traced()) == pytest.approx({
        # idle inside train.loss_sync: [55, 60] in step 0, [108, 110] and
        # [150, 160] in step 1
        "sync_idle_ms.train": 17 / 2 * 1e-6,
        # the trainer ring's vwaits inside train.commit: 4 and 10 ns
        "commit_wait_ms.train": 14 / 2 * 1e-6,
        "optimizer_ms.train": 20 / 2 * 1e-6,
        "compile_s.train": 12.5})


def test_without_the_programs_side_the_readers_give_nothing(capsys):
    p = traced()
    p.trace.spans = {"/host:CPU/main": [("window", 5, 200), ("step", 9, 11)]}
    p.events, p.mapped, p.compile_s = [], False, None
    p.scopes = {"fusion.1": "jit(train_step)/add"}
    assert read_all(p) == dict.fromkeys(READERS)
    # the train step's HLO is there without its scopes: said, not silent
    assert "carries neither the loss nor the optimizer scope" in \
        capsys.readouterr().out
    p.scopes = {}
    assert read_all(p) == dict.fromkeys(READERS)
    assert "optimizer_ms.train" not in capsys.readouterr().out


def test_trainer_transactions_outside_their_commit_span_are_counted():
    p = traced()
    assert programtrace.commit_misses(p, 1) == (0, 6)
    p.events.append(("lw_apply", 185, 195, 1, 4, "#9"))
    assert programtrace.commit_misses(p, 1) == (1, 7)
    assert programtrace.commit_misses(p, 10) == (0, 7)


def test_a_stall_names_its_step_span_and_whether_the_program_ended():
    p = traced()
    st = programtrace.stalls(p, "/host:CPU/main", min_ns=40)
    assert [(s["step"], s["span"], s["program_open"],
             s["program_ended_before_ms"]) for s in st] == [
        (0, "train.commit", False, 0.0), (1, "train.commit", False, 0.0)]
    assert [s["gap_s"] * 1e9 for s in st] == pytest.approx([55, 50])
    p.trace.modules["/device:TPU:0"] = [("jit_train_step", 10, 120)]
    assert programtrace.stalls(p, "/host:CPU/main",
                               min_ns=50)[0]["program_open"]


def test_the_printed_checks_name_the_programs_spans(capsys):
    programtrace._print_checks(traced())
    out = capsys.readouterr().out
    assert "outside their train.commit: 0 of 6" in out
    assert "loss 0.000s, optimizer 0.000s, other 0.000s of busy" in out
    # idle by innermost span, longest first: train.commit 32 ns, commit
    # 28, train 15, ..., host:none 5
    line = next(x for x in out.splitlines() if "idle by innermost" in x)
    names = [part.split()[0] for part in line.split(": ", 1)[1].split(", ")]
    assert names[:3] == ["train.commit", "commit", "train"]
    assert "host:none" in names


def test_op_names_come_from_the_profiles_hlo(tmp_path):
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("loss"):
            y = jnp.sin(x) @ x
        with jax.named_scope("optimizer"):
            return y * 2 + 1

    f = jax.jit(step)
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.stop_trace()
    scopes = programtrace.hlo_scopes(tracefile.find_xplane(str(tmp_path)))
    phases = {programtrace.phase_of(v) for v in scopes.values()
              if v.startswith("jit(step)")}
    assert {"loss", "optimizer"} <= phases
    # a profile that does not parse gives no op names, and raises nothing
    bad = tmp_path / "bad.xplane.pb"
    raw = open(tracefile.find_xplane(str(tmp_path)), "rb").read()
    for cut in (b"\x0a\xff\xff\xff", raw[:len(raw) // 3], b"\x0f" * 64):
        bad.write_bytes(cut)
        assert isinstance(programtrace.hlo_scopes(str(bad)), dict)


def test_a_trainer_run_under_the_profiler_is_gathered_and_mapped(tmp_path,
                                                                 capsys):
    """A tiny ``Trainer.run`` under the CPU profiler: its events map onto
    the trace by the anchor, each of its spans lands on its annotation
    within 50 us, and every commit's transaction events inside it."""
    import jax
    import jax.numpy as jnp

    from repro.data.pipeline import DataConfig
    from repro.models import Backbone, LayerGroup, ModelConfig
    from repro.optim import adamw
    from repro.runtime import profiling
    from repro.runtime.steps import StepSettings
    from repro.runtime.train_loop import Trainer, TrainerConfig

    cfg = ModelConfig(name="program-trace-test", family="dense", d_model=32,
                      n_heads=2, n_kv_heads=1, d_ff=64, vocab=128,
                      groups=(LayerGroup(("attn",), 1),))
    tr = Trainer(Backbone(cfg, compute_dtype=jnp.float32, remat=False),
                 adamw.AdamWConfig(lr=1e-3, total_steps=3),
                 DataConfig(vocab=128, seq_len=8, global_batch=2),
                 TrainerConfig(total_steps=3, ckpt_every=100,
                               ckpt_dir=str(tmp_path / "ckpt"), log_every=100),
                 StepSettings(zero3=False, gather_weights=False, remat=False))
    try:
        state = tr.init_or_restore()
        profiling.drain()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "t"), profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            tr.run(state)
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
    finally:
        tr.shutdown()
    xplane = tracefile.find_xplane(str(tmp_path / "t"))
    base = tracefile.load(xplane, ["window"])
    lo, hi, _, _ = tracefile.window(base)
    run = {"session": SimpleNamespace(trace_dir=str(tmp_path / "t"),
                                      window_s=(t0, t1)),
           "trace": base, "trace_window": (lo, hi)}
    p = programtrace.read(run)
    assert p.mapped and p.compile_s is not None
    commits = programtrace.host_spans(p.trace, "train.commit", lo, hi)
    mine = sorted(e for e in p.events if e[0] == "train.commit")
    assert len(commits) == len(mine) == 3
    for (_, a, b), (_, s, e, _, _, _) in zip(commits, mine):
        # the txtrace span encloses its annotation, within 50 us a side
        assert 0 <= a - s < 50e3 and 0 <= e - b < 50e3
    miss, n = programtrace.commit_misses(p, programtrace.COMMIT_SLACK_NS)
    assert miss == 0 and n >= 3 * 4
    out = capsys.readouterr().out
    assert "mapped by the anchor" in out and "built at traced steps" in out
