"""The program's own trace (``bench/program_trace.py``) and the six
metrics that read it: the decoder on a hand-built XSpace, each reduction
and reader on hand-made events, a parent without names, spans or stamps
(every reader then reports nothing), and a small trace recorded on the
chip (``bench/fixtures/trace_program.json.gz``)."""
import gzip
import importlib
import json
import types

import pytest

from bench import program_trace as ptr
from bench import trace_reduce
from bench.spec import BENCH_DIR

READERS = ("decode_step_ms", "prefill_chunk_ms", "sampler_share",
           "step_idle_ms", "queue_wait_p90_ms", "prefill_wait_p90_ms")
SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.kv.stage",
         "serve.kv.store", "serve.grow", "serve.reserve", "serve.kv.sync",
         "serve.dispatch", "serve.host_sync", "serve.emit")
SAMPLER = "jit(serve_decode_span)/while/body/closed_call/sampler/sort:sort"
LAYER = "jit(serve_decode_span)/while/body/closed_call/dot_general:dot"

# two engine steps in a window of 1000 ns: a decode span of 4 steps
# (100-150 and 300-350 ns), one prefill chunk (160-170 ns) and an eager
# op outside any named program (175-180 ns)
PROGRAM = {
    "modules": [("jit_serve_decode_span(1)", 100, 50),
                ("jit_serve_prefill_chunk(2)", 160, 10),
                ("jit_scatter(3)", 175, 5),
                ("jit_serve_decode_span(1)", 300, 50)],
    "ops": [("fusion.1", 100, 30, LAYER), ("sort.2", 130, 20, SAMPLER),
            ("fusion.3", 160, 10, "jit(serve_prefill_chunk)/add:add"),
            ("scatter.4", 175, 5, "jit(scatter)/scatter:scatter"),
            ("fusion.1", 300, 40, LAYER), ("sort.2", 340, 10, SAMPLER)],
    "host": [("bench.window", 0, 1000), ("bench.step", 80, 120),
             ("serve.step", 90, 100), ("serve.dispatch", 90, 10),
             ("serve.host_sync", 100, 50), ("serve.emit", 150, 40),
             ("bench.step", 280, 100), ("serve.step", 290, 80),
             ("serve.dispatch", 290, 10), ("serve.host_sync", 300, 50),
             ("serve.emit", 350, 20)],
}


class _Step:
    def __init__(self, **stats):
        self.stats = stats


def _req(arrived, admitted=None, first=None):
    r = types.SimpleNamespace(arrived_at=arrived)
    if admitted is not None:
        r.admitted_at, r.first_token_at = admitted, first
    return r


def _run(program=PROGRAM, reqs=()):
    steps = [_Step(decode_steps=8, prefill_chunks=2),
             _Step(decode_steps=12, prefill_chunks=3),
             _Step(decode_steps=16, prefill_chunks=3)]
    recs = [types.SimpleNamespace(counted=True, req=r) for r in reqs]
    return types.SimpleNamespace(program=program, trace={}, steps=steps,
                                 trace_steps=(1, 3), recs=recs)


def _read(name, run):
    return importlib.import_module(f"bench.metrics.{name}").read(run)


def test_reductions_on_hand_made_events():
    assert ptr.module_seconds(PROGRAM, 0, 1000, "serve_decode_span") == (
        pytest.approx(100e-9), 2)
    # clipped to the window
    assert ptr.module_seconds(PROGRAM, 0, 120, "serve_decode_span")[0] == \
        pytest.approx(20e-9)
    split = ptr.module_split(PROGRAM, 0, 1000)
    assert split["jit_serve_decode_span"] == pytest.approx(100e-9)
    assert split["unnamed"] == pytest.approx(5e-9)
    busy = trace_reduce.busy_ns([e[:3] for e in PROGRAM["ops"]], 0, 1000)
    named = sum(v for k, v in split.items() if k.startswith("jit_serve_"))
    assert named + split["unnamed"] == pytest.approx(busy * 1e-9)
    assert ptr.scope_seconds(PROGRAM, 0, 1000, "serve_decode_span",
                             "sampler") == pytest.approx(30e-9)
    assert ptr.scope_seconds(PROGRAM, 0, 1000, "serve_select",
                             "sampler") == 0
    assert ptr.in_scope(SAMPLER, "serve_decode_span", "sampler")
    assert not ptr.in_scope(LAYER, "serve_decode_span", "sampler")
    # idle inside the steps: 90-100, 150-160, 170-175, 180-190 and
    # 290-300, 350-370
    steps = ptr.host_spans(PROGRAM, "serve.step")
    dev = [(s, s + d) for _, s, d, _ in PROGRAM["ops"]]
    assert ptr.idle_inside(dev, steps, 0, 1000) == pytest.approx(65e-9)
    assert ptr.idle_inside(dev, steps, 0, 200) == pytest.approx(35e-9)


def test_stats_delta_over_the_traced_steps():
    run = _run()
    assert ptr.stats_delta(run, "decode_steps") == 8
    assert ptr.stats_delta(run, "prefill_chunks") == 1
    run.trace_steps = (0, 2)
    assert ptr.stats_delta(run, "decode_steps") == 12
    run.trace_steps = (2, 2)
    assert ptr.stats_delta(run, "decode_steps") == 0


def test_readers_on_hand_made_events(capsys):
    reqs = [_req(0.0, 0.0, 1.0), _req(1.0, 1.5, 2.0), _req(2.0, 3.0, 6.0),
            _req(3.0)]                       # never admitted: not counted
    run = _run(reqs=reqs)
    assert _read("decode_step_ms", run) == pytest.approx(100e-6 / 8)
    assert _read("prefill_chunk_ms", run) == pytest.approx(10e-6 / 1)
    assert _read("sampler_share", run) == pytest.approx(30.0)
    assert _read("step_idle_ms", run) == pytest.approx(65e-6 / 2)
    # waits for a slot 0, 0.5, 1.0 s; to the first token 1, 0.5, 3 s
    assert _read("queue_wait_p90_ms", run) == pytest.approx(900.0)
    assert _read("prefill_wait_p90_ms", run) == pytest.approx(2600.0)
    err = capsys.readouterr().err
    # every ratio prints its base; the idle is put down to serve.* spans
    assert "over 8 decode steps" in err and "in 2 serve.step spans" in err
    assert "serve.emit" in err and "unnamed" in err


def test_a_program_without_names_spans_or_stamps_reports_nothing():
    """The parent program under this benchmark: its programs are all
    ``jit__lambda``, it writes no serve.* span and its requests carry no
    stamps. Every reader returns None and none raises."""
    bare = {"modules": [("jit__lambda(9)", 100, 50)],
            "ops": [("fusion.1", 100, 50, "jit(<lambda>)/while:while")],
            "host": [("bench.window", 0, 1000), ("bench.step", 90, 100)]}
    run = _run(bare, [types.SimpleNamespace(arrived_at=0.0)])
    assert all(_read(m, run) is None for m in READERS)
    empty = {"modules": [], "ops": [], "host": [("bench.window", 0, 10)]}
    assert all(_read(m, _run(empty)) is None for m in READERS)
    untraced = types.SimpleNamespace(trace=None, recs=[])
    assert ptr.of(untraced) is None


def test_decoder_reads_a_hand_built_xspace(tmp_path):
    """Ops take their scope from the ``tf_op`` stat of their metadata,
    held as a string or as a reference to a stat name; times are the
    line's timestamp plus the event's offset; host events keep only the
    benchmark's and the engine's spans."""
    space = ptr._messages()["XSpace"]()
    dev = space.planes.add(name="/device:TPU:0")
    for k, name in ((1, "tf_op"), (2, "flops"), (3, SAMPLER)):
        e = dev.stat_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    for k, name in ((10, "jit_serve_decode_span(7)"), (11, "%fusion.1"),
                    (12, "%sort.2")):
        e = dev.event_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    dev.event_metadata[1].value.stats.add(metadata_id=1, str_value=LAYER)
    dev.event_metadata[1].value.stats.add(metadata_id=2, int64_value=5)
    dev.event_metadata[2].value.stats.add(metadata_id=1, ref_value=3)
    mod = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    mod.events.add(metadata_id=10, offset_ps=0, duration_ps=50_000)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=11, offset_ps=500, duration_ps=30_000)
    ops.events.add(metadata_id=12, offset_ps=30_500, duration_ps=19_500)
    host = space.planes.add(name="/host:CPU")
    for k, name in ((1, "serve.step"), (2, "bench.window"),
                    (3, "PjitFunction(serve_decode_span)")):
        e = host.event_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    ln = host.lines.add(name="python", timestamp_ns=900)
    for k in (1, 2, 3):
        ln.events.add(metadata_id=k, offset_ps=k * 1000, duration_ps=99_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    t = ptr.load(str(path))
    assert t["modules"] == [("jit_serve_decode_span(7)", 1000.0, 50.0)]
    assert t["ops"] == [("%fusion.1", 1000.5, 30.0, LAYER),
                        ("%sort.2", 1030.5, 19.5, SAMPLER)]
    assert t["host"] == [("serve.step", 901.0, 99.0),
                         ("bench.window", 902.0, 99.0)]


SAMPLER_PATH = "jit(serve_decode_span)/while/body/closed_call/sampler"


def _module(*instructions, fused=()):
    """An ``HloModuleProto``: a computation ``body`` (id 1) holding
    ``(id, name, opcode, op_name, operand ids[, called ids])`` and a fused
    computation (id 2) holding ``fused``."""
    mod = ptr._messages()["HloModuleProto"](name="jit_serve_decode_span")
    for cid, rows in ((1, instructions), (2, fused)):
        comp = mod.computations.add(name=f"c{cid}", id=cid)
        for k, name, opcode, op_name, operands, *called in rows:
            i = comp.instructions.add(id=k, name=name, opcode=opcode)
            i.metadata.op_name = op_name
            i.operand_ids.extend(operands)
            i.called_computation_ids.extend(called[0] if called else ())
    return mod


# the keep-mask scatter as the TPU compiler rewrites it: a sort and a
# fusion with no op_name of their own, fed by the sampler's instructions
# and a constant that an unrelated op made first
KEEP_MASK = _module(
    (1, "param.1", "parameter", "", []),
    (2, "constant.2", "constant", "jit(serve_decode_span)/jit(_take)", []),
    (3, "iota.3", "iota", f"{SAMPLER_PATH}/iota", []),
    (4, "fusion.4", "fusion", f"{SAMPLER_PATH}/scatter", [1], [2]),
    (5, "reshape.5", "reshape", "", [3]),
    (6, "sort.6", "sort", "", [5, 4, 2]),
    (7, "get-tuple-element.7", "get-tuple-element", "", [6]),
    (8, "fusion.8", "fusion", "", [7, 2], [2]),
    (9, "copy.9", "copy", "", [1]),
    (10, "dot.10", "dot", "jit(serve_decode_span)/while/body/closed_call/"
     "dot_general", [1]),
    (11, "fusion.11", "fusion", "", [8, 10], [2]),
    (12, "while.12", "while", "", [8]),
    fused=[(20, "add.20", "add", "", [])])


def test_compiler_made_ops_are_charged_by_what_they_compute_from():
    """An op without a program scope takes the path common to the scopes
    it is computed from: the rewritten keep mask lands in the sampler,
    an op fed by the sampler and the LM head in neither. Constants,
    parameters, loops and fused instructions are not charged."""
    got = ptr.charged_scopes(KEEP_MASK)
    assert got["sort.6"] == got["fusion.8"] == SAMPLER_PATH
    assert got["reshape.5"] == f"{SAMPLER_PATH}/iota"
    assert got["fusion.11"] == "jit(serve_decode_span)/while/body/closed_call"
    assert ptr.in_scope(got["sort.6"], "serve_decode_span", "sampler")
    assert not ptr.in_scope(got["fusion.11"], "serve_decode_span",
                            "sampler")
    # ops with a scope of their own keep it; nothing derives from param.1
    # or constant.2 alone; a loop's event holds others; add.20 runs inside
    # fusion.8's event
    assert set(got) == {"reshape.5", "sort.6", "get-tuple-element.7",
                        "fusion.8", "fusion.11"}


def test_decoder_charges_ops_from_the_trace_hlo(tmp_path):
    """Where the trace holds a program's HLO (``/host:metadata``), an op
    whose instruction has no program scope is charged by
    ``charged_scopes`` in place of the enclosing loop's ``tf_op``; the op
    is matched to its program by the module event it runs in."""
    space = ptr._messages()["XSpace"]()
    dev = space.planes.add(name="/device:TPU:0")
    e = dev.stat_metadata.add(key=1)
    e.value.id, e.value.name = 1, "tf_op"
    names = {10: "jit_serve_decode_span(7)", 11: "jit_other(8)",
             12: "%sort.6 = (s32[8]{0}, pred[8]{0}) sort(...)",
             13: "%fusion.4 = pred[8]{0} fusion(...)"}
    for k, name in names.items():
        e = dev.event_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    loop = "jit(serve_decode_span)/while:while"
    dev.event_metadata[2].value.stats.add(metadata_id=1, str_value=loop)
    dev.event_metadata[3].value.stats.add(
        metadata_id=1, str_value=f"{SAMPLER_PATH}/scatter:scatter")
    mod = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    mod.events.add(metadata_id=10, offset_ps=0, duration_ps=100_000)
    mod.events.add(metadata_id=11, offset_ps=200_000, duration_ps=100_000)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for k, t in ((13, 10), (12, 50), (12, 250)):
        ops.events.add(metadata_id=k, offset_ps=t * 1000,
                       duration_ps=10_000)
    meta = space.planes.add(name="/host:metadata")
    e = meta.stat_metadata.add(key=1)
    e.value.id, e.value.name = 1, "Hlo Proto"
    e = meta.event_metadata.add(key=7)
    e.value.id, e.value.name = 7, "jit_serve_decode_span(7)"
    hlo = ptr._messages()["HloProto"](hlo_module=KEEP_MASK)
    e.value.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())

    t = ptr.load(str(path))
    assert [o[3] for o in t["ops"]] == [
        f"{SAMPLER_PATH}/scatter:scatter", SAMPLER_PATH,
        # the same instruction name in a program without HLO here
        loop]
    assert ptr.scope_seconds(t, 0, 1000, "serve_decode_span",
                             "sampler") == pytest.approx(20e-9)


def _fixture():
    with gzip.open(BENCH_DIR / "fixtures" / "trace_program.json.gz",
                   "rt") as f:
        d = json.load(f)
    return {k: [tuple(e) for e in d[k]] for k in ("modules", "ops", "host")}


def test_chip_trace_holds_every_span_program_and_scope():
    t = _fixture()
    assert set(SPANS) <= {n for n, _, _ in t["host"]}
    names = {n.split("(")[0] for n, _, _ in t["modules"]}
    assert {"jit_serve_decode_span", "jit_serve_prefill_chunk"} <= names
    lo, hi = ptr.window(t)
    span, n = ptr.module_seconds(t, lo, hi, "serve_decode_span")
    samp = ptr.scope_seconds(t, lo, hi, "serve_decode_span", "sampler")
    assert n == 1 and 0 < samp < span
    # the keep mask the compiler rewrote into a sort and a fusion is
    # charged to the sampler, not to the loop around it
    scope = {op: sc for op, _, _, sc in t["ops"]}
    assert all(ptr.in_scope(scope[op], "serve_decode_span", "sampler")
               for op in ("%sort.24", "%sort.26", "%fusion.197",
                          "%fusion.203"))
    assert 15 < 100 * samp / span < 30
    # the paged kernel is found by its name
    from bench.metrics import paged_attn_roofline
    assert any(paged_attn_roofline.is_kernel(op) for op, *_ in t["ops"])


def test_chip_trace_programs_and_remainder_make_busy():
    t = _fixture()
    lo, hi = ptr.window(t)
    split = ptr.module_split(t, lo, hi)
    busy = trace_reduce.busy_ns([e[:3] for e in t["ops"]], lo, hi) * 1e-9
    named = sum(v for k, v in split.items() if k.startswith("jit_serve_"))
    # a program's event also holds the launch edges around its first and
    # last op: well under a microsecond each
    assert abs(named + split["unnamed"] - busy) < len(t["modules"]) * 1e-6
    steps = ptr.host_spans(t, "serve.step")
    dev = [(s, s + d) for _, s, d, _ in t["ops"]]
    idle = ptr.idle_inside(dev, steps, lo, hi)
    gaps = trace_reduce.gaps_by_host([e[:3] for e in t["ops"]], t["host"],
                                     lo, hi)
    # the idle inside the engine step is put down to its serve.* spans
    serve = sum(v for k, v in gaps.items() if k.startswith("serve."))
    assert 0 < idle and serve == pytest.approx(idle, rel=1e-6)
