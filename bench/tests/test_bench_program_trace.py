"""The reduction from trace events to the program's scopes, levels, idle
time by span and host self time, its script, and the readers of the
set-up metrics."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import program_trace as pt  # noqa: E402
from bench.registry import Registry  # noqa: E402

MS = 1_000_000
LOOP = "jit(uncoarsen_level)/vmap(jit(_refine_loop))/while"
NEW_METRICS = ("setup_lower_s.oneshot", "setup_compile_s.oneshot")


@pytest.mark.parametrize("path, scope", [
    (f"{LOOP}/body/jet.rs/jit(searchsorted)/vmap()/while/body/gather",
     "jet.rs"),
    (f"{LOOP}/body/jet.queries/reduce_max", "jet.queries"),
    ("jit(uncoarsen_level)/vmap(uncoarsen.project)/jit(clip)/max",
     "uncoarsen.project"),
    ("jit(uncoarsen_level_fleet)/vmap(vmap(uncoarsen.build_state))/lt",
     "uncoarsen.build_state"),
    ("jit(_initial_batch)/initial/vmap(while)/body/add", "initial"),
    (f"{LOOP}/body/select_n", "other"),
    ("jit(initial_partition)/add", "other"),
    ("", "other"),
])
def test_scope_is_the_innermost_named_component(path, scope):
    assert pt.scope_of(path) == scope


def test_self_time_leaves_out_nested_events():
    events = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"),
              (50, 60, "c"), (200, 210, "d")]
    assert pt.self_times(events) == [100 - 20 - 50, 20, 50 - 10, 10, 10]


def test_scope_table_sums_to_the_busy_time_in_the_modules():
    ops = [(0, 100 * MS, LOOP),                         # holds the next two
           (10 * MS, 30 * MS, f"{LOOP}/body/jet.lp/x"),
           (40 * MS, 90 * MS, f"{LOOP}/body/jet.apply/y"),
           (120 * MS, 130 * MS, "jit(uncoarsen_level)/vmap("
                                "uncoarsen.build_state)/z"),
           (300 * MS, 310 * MS, f"{LOOP}/body/jet.lp/x")]  # other module
    modules = [(0, 200 * MS)]
    scopes, per_module = pt.scope_table(ops, modules)
    assert scopes == pytest.approx({"other": 0.030, "jet.lp": 0.020,
                                    "jet.apply": 0.050,
                                    "uncoarsen.build_state": 0.010})
    assert per_module == pytest.approx([0.110])
    assert sum(scopes.values()) == pytest.approx(sum(per_module))


def test_levels_match_modules_to_spans_in_order():
    parts = [(0, 100), (100, 200)]
    level_spans = [(1, 2, 2), (3, 4, 1), (5, 6, 0),   # first partition
                   (101, 102, 1), (103, 104, 0)]      # second partition
    modules = [(10, 20), (20, 40), (40, 90), (110, 120), (120, 180),
               (185, 190)]                           # one module too many
    module_s = [0.01, 0.02, 0.05, 0.01, 0.06, 0.005]
    assert pt.match_levels(parts, level_spans, modules, module_s) == [
        [[2, 0.01], [1, 0.02], [0, 0.05]], None]


def test_idle_time_by_innermost_program_span():
    ops = [(0, 10 * MS), (30 * MS, 40 * MS), (60 * MS, 70 * MS),
           (100 * MS, 110 * MS)]
    spans = [(0, 100 * MS, "partition"),
             (12 * MS, 28 * MS, "coarsen.fetch"),
             (50 * MS, 80 * MS, "partition.uncoarsen")]
    idle = pt.idle_by_span(ops, spans, 0, 160 * MS)
    assert idle == pytest.approx({"coarsen.fetch": 0.020,
                                  "partition.uncoarsen": 0.020,
                                  "partition": 0.030, pt.NO_SPAN: 0.050})
    assert sum(idle.values()) == pytest.approx(0.160 - 0.040)


def test_host_self_time_per_span_name():
    spans = [(0, 100, "partition", 0), (0, 40, "partition.coarsen", 0),
             (5, 15, "coarsen.level", 0), (20, 30, "coarsen.level", 0),
             (50, 90, "partition.uncoarsen", 0),
             (60, 95, "serve.dispatch", 1)]      # another thread
    got = pt.host_self(spans)
    assert got == pytest.approx({
        "partition": (100 - 40 - 40) / 1e9,
        "partition.coarsen": (40 - 20) / 1e9, "coarsen.level": 20 / 1e9,
        "partition.uncoarsen": 40 / 1e9, "serve.dispatch": 35 / 1e9})


def test_summarize_over_one_window():
    unc = "jit_uncoarsen_level"
    ops = [(0, 10 * MS, "jit(coarsen_level)/x"),
           (12 * MS, 15 * MS, "jit(_initial_batch)/initial/vmap(while)/x"),
           (15 * MS, 16 * MS, "jit(_initial_batch)/copy"),
           (20 * MS, 50 * MS, f"{LOOP}/body/jet.lp/x"),
           (50 * MS, 60 * MS, f"{LOOP}/body/jet.rw/x"),
           (70 * MS, 90 * MS, f"{LOOP}/body/jet.rs/x"),
           (500 * MS, 510 * MS, f"{LOOP}/body/jet.lp/x")]  # outside
    modules = [(0, 10 * MS, "jit_coarsen_level(3)"),
               (12 * MS, 16 * MS, "jit__initial_batch(5)"),
               (20 * MS, 60 * MS, f"{unc}(7)"), (70 * MS, 90 * MS, unc)]
    spans = [(0, 100 * MS, "partition", 0, {"k": 8}),
             (15 * MS, 61 * MS, "uncoarsen.level", 0, {"level": 1}),
             (65 * MS, 91 * MS, "uncoarsen.level", 0, {"level": 0})]
    s = pt.summarize(ops, modules, spans, 0, 100 * MS)
    assert s["scopes"] == pytest.approx({"jet.lp": 0.03, "jet.rw": 0.01,
                                         "jet.rs": 0.02})
    assert s["uncoarsen_s"] == pytest.approx(0.06)
    assert s["initial_s"] == pytest.approx(0.004)
    assert s["initial_scopes"] == pytest.approx({"initial": 0.003,
                                                 "other": 0.001})
    assert s["levels"] == [[[1, pytest.approx(0.04)],
                            [0, pytest.approx(0.02)]]]
    assert s["partitions"] == 1
    assert sum(s["idle_by_span"].values()) == pytest.approx(0.1 - 0.074)
    assert set(s["host_self"]) == {"partition", "uncoarsen.level"}


def test_a_trace_without_program_spans_gives_no_levels(tmp_path):
    ops = [(0, 10, f"{LOOP}/x")]
    s = pt.summarize(ops, [(0, 10, "jit_uncoarsen_level")], [], 0, 20)
    assert s["levels"] == [] and s["partitions"] == 0
    assert s["initial_s"] == 0 and s["initial_scopes"] == {}
    assert s["scopes"] == {"other": pytest.approx(1e-8)}
    assert pt.reduce_trace(str(tmp_path), "bench.window") == {}


def _counters(monkeypatch, counts):
    """The process's compile counters, as the program has summed them."""
    from repro.launch import compile_cache

    stats = compile_cache.CompileCacheStats()
    stats.counts = dict(counts)
    monkeypatch.setattr(compile_cache, "_CACHE_STATS", stats)


def test_new_readers_read_their_keys(monkeypatch):
    _counters(monkeypatch, {"trace_s": 3.0, "lower_s": 2.0,
                            "compile_s": 9.0, "compiles": 4})
    reg = Registry()
    got = {m: reg.metric(m).read({"trace": {}}) for m in NEW_METRICS}
    assert got == pytest.approx({"setup_lower_s.oneshot": 5.0,
                                 "setup_compile_s.oneshot": 9.0})


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_without_their_key(metric, monkeypatch):
    # a program that counts neither, as before any compile
    _counters(monkeypatch, {})
    assert Registry().metric(metric).read({"trace": {}}) is None


def test_an_older_program_gives_no_lowering_time(monkeypatch):
    # compile seconds only, as a program without trace and lower counters
    _counters(monkeypatch, {"compile_s": 9.0, "compiles": 4})
    reg = Registry()
    assert reg.metric("setup_lower_s.oneshot").read({"trace": {}}) is None
    assert reg.metric("setup_compile_s.oneshot").read(
        {"trace": {}}) == 9.0


def test_the_script_gives_no_result_without_a_tpu():
    root = Path(__file__).resolve().parents[2]
    p = subprocess.run(
        [sys.executable, "bench/program_trace.py", "--workload",
         "delaunay.oneshot.k64", "--seed", "3000000007", "--seconds", "1"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of ``(number, value)`` fields: an int is a
    varint, bytes or a str is length-delimited."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _instruction(name: str, op_name: str) -> bytes:
    return _msg((1, name), (2, "fusion"), (3, _msg((1, 4))),
                (7, _msg((1, "add"), (2, op_name))))


def test_hlo_op_names_read_from_the_metadata_plane():
    hlo = _msg((1, _msg((1, "jit_uncoarsen_level"), (3, _msg(
        (1, "main"),
        (2, _instruction("fusion.3", f"{LOOP}/body/jet.lp/add")),
        (2, _instruction("while.1", LOOP)))))))
    meta = _msg((1, 2 ** 63 + 5),
                (2, "jit_uncoarsen_level(9223372036854775813)"),
                (5, _msg((1, 1), (6, hlo))))
    space = _msg(
        (1, _msg((1, 7), (2, "/device:TPU:0"),
                 (4, _msg((1, 3), (2, _msg((1, 3), (2, "fusion.3"))))))),
        (1, _msg((2, pt.METADATA_PLANE), (4, _msg((1, 5), (2, meta))),
                 (5, _msg((1, 1), (2, _msg((1, 1), (2, "Hlo Proto"))))))))
    names = pt.hlo_op_names(space)
    want = {"fusion.3": f"{LOOP}/body/jet.lp/add", "while.1": LOOP}
    assert names == {"jit_uncoarsen_level(9223372036854775813)": want,
                     str(2 ** 63 + 5): want}


def test_ops_take_the_path_of_their_instruction_in_their_module():
    names = {"jit_uncoarsen_level(11)": {"fusion.3": "a/jet.lp/x"},
             "12": {"fusion.3": "b/jet.rs/y"}}
    modules = [(0, 10, "jit_uncoarsen_level(11)"),
               (20, 30, "jit_uncoarsen_level(12)")]
    ops = [(1, 2, "%fusion.3 = s32[4096]{0} fusion(s32[] %p), kind=kLoop"),
           (3, 4, "%copy.1 = s32[8]{0} copy(s32[8]{0} %q)"),
           (21, 22, "%fusion.3 = s32[64]{0} fusion(s32[] %p), kind=kLoop"),
           (40, 41, "%fusion.3 = s32[64]{0} fusion(s32[] %p), kind=kLoop")]
    assert pt.resolve_paths(ops, modules, names) == [
        (1, 2, "a/jet.lp/x"), (3, 4, ""), (21, 22, "b/jet.rs/y"),
        (40, 41, "")]
