"""Tests of the stage and phase readers (``bench/stages.py``) on synthetic
traces: device stages from the ``op_name`` metadata of op events, idle
time attributed to the chunk driver's phase spans."""
from __future__ import annotations

from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import run as br
from bench import stages
from bench import trace as btr

KERNEL = ('%custom-call.4 = s32[1,256] custom-call(), custom_call_target='
          '"tpu_custom_call", metadata={op_name="jit(run)/while/body/'
          'rosella.dispatch/jit(ppot_dispatch_fused_alias)/pallas_call"}')


def _op(name, scope):
    return (f'%{name} = f64[8] {name.split(".")[0]}(%p), '
            f'metadata={{op_name="{scope}" source_file="scanloop.py"}}')


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in ev]) for ln, ev in lines.items()])


def _stage_trace():
    host = _plane("/host:CPU", {"python": [(btr.WINDOW_SPAN, 100, 1000)]})
    dev = _plane("/device:TPU:0", {btr.OPS_LINE: [
        (_op("while.1", "jit(run)/while"), 150, 500),  # the turn loop
        (_op("while.2", "jit(run)/while/body/rosella.pool_chain/while"),
         200, 200),
        # a scope nested in another: the innermost one wins
        (_op("fusion.3", "jit(run)/while/body/rosella.pool_chain/while/"
             "body/rosella.flush/add"), 250, 10),
        (_op("sort.4", "jit(run)/while/body/rosella.flush/sort"), 420, 50),
        (_op("fusion.5", "jit(run)/while/body/add"), 480, 20),  # unscoped
        ("%fusion.6 = f32[8] fusion(%p)", 500, 30),  # no metadata at all
        (KERNEL, 600, 5),
    ]})
    return btr.reduce_planes([host, dev])


def _ctx(trace, turns=1):
    return {"trace": trace, "host": {"turns": turns, "gen_s": 0.0,
                                     "window_s": trace.window_s},
            "cell": br.Cell("s2x4-poisson-bulk"),
            "device_kind": "TPU v5 lite"}


def test_stage_of_reads_the_innermost_scope():
    assert stages.stage_of(_op("sort.4", "a/rosella.flush/sort")) == "flush"
    assert stages.stage_of(_op(
        "f.1", "a/rosella.pool_chain/b/rosella.flush/c")) == "flush"
    assert stages.stage_of(_op("f.1", "a/rosella.notastage/c")) is None
    assert stages.stage_of(_op("f.1", "jit(run)/while")) is None
    assert stages.stage_of("%fusion.6 = f32[8] fusion(%p)") is None
    assert stages.stage_of(KERNEL) == "dispatch"


def test_stage_times_on_synthetic_trace():
    tr = _stage_trace()
    ctx = _ctx(tr)
    ns = 1e-9
    assert stages.stage_seconds(ctx, "pool_chain") == pytest.approx(200 * ns)
    assert stages.stage_seconds(ctx, "flush") == pytest.approx(60 * ns)
    assert stages.stage_seconds(ctx, "dispatch") == pytest.approx(5 * ns)
    # the turn loop and unscoped ops count in no stage
    for s in ("learner_fold", "alias_build", "pending_append",
              "telemetry_fold"):
        assert stages.stage_seconds(ctx, s) == 0.0
    total = 0.0
    for s in stages.STAGES:
        t = stages.stage_seconds(ctx, s)
        assert t <= tr.busy_s
        total += t
    assert total == pytest.approx(265 * ns)
    assert tr.busy_s == pytest.approx(500 * ns)


def test_stage_readers_need_scopes_and_whole_turns():
    tr = _stage_trace()
    for s in stages.STAGES:
        reader = br.load_module(br.BENCH / "metrics" / f"{s}_us.py")
        assert reader.read(_ctx(tr)) == pytest.approx(
            1e6 * stages.stage_seconds(_ctx(tr), s))
        # the kernel's event for one turn of two: nested events dropped
        assert reader.read(_ctx(tr, turns=2)) is None
    # a program that names no stage: every stage reader is absent
    tr.devices[0].names = np.asarray(
        [n.split(", metadata=")[0] if "custom-call" not in n
         else n.replace("rosella.dispatch/", "")
         for n in tr.devices[0].names], dtype=object)
    for s in stages.STAGES:
        reader = br.load_module(br.BENCH / "metrics" / f"{s}_us.py")
        assert reader.read({**_ctx(tr), "cell": NS(name="no-such-cell")}) \
            is None


def test_stages_from_the_traces_hlo_protos(tmp_path):
    """Where op events name their instruction without its metadata (a TPU
    trace), the stage comes from the module's HLO proto in the trace."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("rosella.flush"):
            x = jnp.sort(x)
        with jax.named_scope("rosella.pool_chain"):
            x = jax.lax.fori_loop(0, 4, lambda i, y: y * 2.0 + 1.0, x)
        return x

    x = jnp.arange(16.0)
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    protos = stages.hlo_op_names(path.read_bytes())
    module = next(m for m in protos if m.startswith("jit_step("))
    by_stage = {}
    for ins, op in protos[module].items():
        by_stage.setdefault(stages.scope_stage(op), []).append(ins)
    assert {"flush", "pool_chain"} <= set(by_stage)
    flush_op, chain_op = by_stage["flush"][0], by_stage["pool_chain"][0]
    plain_op = by_stage[None][0]

    dev = _plane("/device:TPU:0", {btr.OPS_LINE: [
        (f"%{flush_op} = f32[16] op(%p)", 100, 10),
        (f"%{chain_op} = f32[16] op(%p)", 120, 30),
        (f"%{plain_op} = f32[16] op(%p)", 160, 5),
        (f"%{chain_op} = f32[16] op(%p)", 400, 7),  # in another module
        (f"%{chain_op} = f32[16] op(%p)", 600, 9),  # in no module
    ]})
    host = _plane("/host:CPU", {"python": [(btr.WINDOW_SPAN, 0, 1000)]})
    tr = btr.reduce_planes([host, dev])
    modules = {"/device:TPU:0": (np.array([90.0, 390.0]),
                                 np.array([200.0, 500.0]),
                                 [module, "jit_other(99)"])}
    (labels,) = stages.op_stages(tr, protos, modules)
    assert labels.tolist() == ["flush", "pool_chain", None, None, None]


def test_merge_and_overlap():
    s, e = stages.merge([5.0, 0.0, 2.0, 8.0], [9.0, 3.0, 4.0, 10.0])
    assert list(zip(s, e)) == [(0.0, 4.0), (5.0, 10.0)]
    a = (np.array([0.0, 10.0]), np.array([5.0, 20.0]))
    assert stages.overlap(*a, np.array([3.0, 12.0]), np.array([11.0, 30.0])) \
        == pytest.approx(2.0 + 1.0 + 8.0)
    assert stages.overlap(*a, np.array([]), np.array([])) == 0.0
    assert stages.overlap(np.array([]), np.array([]), *a) == 0.0


def _call(start, bounds):
    """One call from ``start``: its span and its five phases, the phase
    ``i`` ending at ``bounds[i]``."""
    spans = [(stages.CALL, start, bounds[-1] - start)]
    t = start
    for phase, end in zip(stages.PHASES, bounds):
        spans.append((stages.PREFIX + phase, t, end - t))
        t = end
    return spans


def _phase_trace(tile: bool):
    first = _call(100, [120, 140, 160, 560, 600])
    second = _call(600, [700, 720, 740, 1000, 1100 if tile else 1050])
    host = _plane("/host:CPU", {"python": [(btr.WINDOW_SPAN, 100, 1000)]
                                + first + second})
    dev0 = _plane("/device:TPU:0", {btr.OPS_LINE: [
        ("while.1", 150, 500), ("fusion.2", 800, 100)]})
    dev1 = _plane("/device:TPU:1", {btr.OPS_LINE: [("while.1", 170, 300)]})
    return btr.reduce_planes([host, dev0, dev1])


@pytest.mark.parametrize("tile", [True, False])
def test_idle_phases_account_for_idle_share(tile):
    tr = _phase_trace(tile)
    ctx = {"trace": tr, "host": {"turns": 2}}
    idle = br.load_module(
        br.BENCH / "metrics" / "idle_share.online.py").read(ctx)
    shares = {}
    for p in stages.PHASES:
        reader = br.load_module(br.BENCH / "metrics" / f"idle_{p}.online.py")
        shares[p] = reader.read(ctx)
        assert 0.0 <= shares[p] <= idle
    if tile:
        assert sum(shares.values()) == pytest.approx(idle)
    else:  # the last 50 ns of the window lie in no phase
        assert sum(shares.values()) == pytest.approx(idle - 100 * 50 / 1000)
    # device 0 is idle in [100,150), [650,800), [900,1100) and device 1
    # in [100,170), [470,1100); the fences [160,560) and [740,1000) hold
    # 60 + 100 ns of the first and 10 + 90 + 260 ns of the second
    assert shares["fence"] == pytest.approx(0.5 * (16.0 + 36.0))


def test_idle_phases_absent_without_driver_spans():
    host = _plane("/host:CPU", {"python": [(btr.WINDOW_SPAN, 100, 1000)]})
    dev = _plane("/device:TPU:0", {btr.OPS_LINE: [("while.1", 150, 500)]})
    ctx = {"trace": btr.reduce_planes([host, dev]), "host": {"turns": 1}}
    for p in stages.PHASES:
        reader = br.load_module(br.BENCH / "metrics" / f"idle_{p}.online.py")
        assert reader.read(ctx) is None
