"""Device time by program scope: the scope rule, the reading of a trace's
event metadata, the innermost-op charge, and the readers of the scope
shares, on synthetic metadata and spans."""

import os
import types

import pytest

import tiny_cells  # noqa: F401  (puts the benchmark on the path)
import spec
import trace_reduce as T


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/transpose(jvp(attn))/spm/mul", "spm"),
    ("jit(step)/transpose(jvp(attn))/dot_general", "attn"),
    ("jit(step)/jvp(ffn)/spm/while/body/mul", "spm"),
    ("jit(tick)/sample/sort;jit(tick)/embed/convert_element_type", "sample"),
    ("jit(tick)/add;jit(tick)/embed/convert_element_type", ""),
    ("jit(step)/mul", ""),
    ("jit(attn)/pjit(spm)/mul", ""),
    ("", ""),
])
def test_scope_is_the_innermost_table_name(op_name, scope):
    assert T.scope_of(op_name) == scope


def _xspace(events, modules=()):
    """An XSpace with one TPU plane: ``events`` are (name, tf_op or None,
    start_ps, duration_ps) on the line "XLA Ops", each with the stat
    ``hlo_module``; a host plane holds ``bench.window`` over them."""
    xp = T._xplane_pb2()
    space = xp.XSpace()
    pl = space.planes.add(id=1, name="/device:TPU:0")
    for i, key in enumerate(("tf_op", "hlo_module"), 1):
        pl.stat_metadata[i].id, pl.stat_metadata[i].name = i, key
    line = pl.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    for i, (name, tf_op, start, dur) in enumerate(events, 1):
        md = pl.event_metadata[i]
        md.id, md.name = i, name
        if tf_op is not None:
            md.stats.add(metadata_id=1, str_value=tf_op)
        ev = line.events.add(metadata_id=i, offset_ps=start,
                             duration_ps=dur)
        ev.stats.add(metadata_id=2, str_value="jit_step")
    host = space.planes.add(id=2, name="/host:CPU")
    host.event_metadata[1].id, host.event_metadata[1].name = 1, \
        "bench.window"
    hl = host.lines.add(id=1, name="python", timestamp_ns=1000)
    hl.events.add(metadata_id=1, offset_ps=0,
                  duration_ps=max(s + d for _, _, s, d in events))
    return space


def _write(tmp_path, space):
    d = tmp_path / "plugins" / "profile" / "t0"
    d.mkdir(parents=True)
    (d / "x.xplane.pb").write_bytes(space.SerializeToString())
    return str(tmp_path)


US = 1_000_000   # picoseconds in a microsecond
EVENTS = [
    ("%while.1 = (s32[]) while(%tuple.2)", "jit(step)/while", 0, 100 * US),
    ("%fusion.3 = bf16[8] fusion(%spm_call.2)",
     "jit(step)/transpose(jvp(attn))/mul", 10 * US + 123_456, 20 * US),
    ("%spm_stack_kernel_call.4 = bf16[8] custom-call()",
     "jit(step)/transpose(jvp(attn))/spm/pallas_call", 40 * US, 30 * US),
    ("%fusion.5 = f32[8] fusion()",
     "jit(step)/embed/convert;jit(step)/attn/mul", 110 * US, 5 * US + 999),
    ("%copy.6 = f32[8] copy()", None, 120 * US, 10 * US),
]


def test_load_gives_each_op_its_scope_and_reads_as_profile_data(tmp_path):
    from jax.profiler import ProfileData
    d = _write(tmp_path, _xspace(EVENTS))
    tr = T.load(d)
    ops = tr.device_ops["/device:TPU:0"]
    assert [(o.name, o.scope, o.module) for o in ops] == [
        ("while.1", "", "jit_step"), ("fusion.3", "attn", "jit_step"),
        ("spm_stack_kernel_call.4", "spm", "jit_step"),
        ("fusion.5", "embed", "jit_step"), ("copy.6", "", "jit_step")]
    # the names, times and modules jax.profiler.ProfileData reads
    pd = ProfileData.from_file(os.path.join(
        d, "plugins", "profile", "t0", "x.xplane.pb"))
    seen = [(T.op_name(ev.name), ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9,
             dict(ev.stats).get("hlo_module"))
            for p in pd.planes if p.name == "/device:TPU:0"
            for line in p.lines for ev in line.events]
    assert [(o.name, o.start, o.end, o.module) for o in ops] == seen
    assert tr.window() == pytest.approx((1e-6, 131e-6))


def test_time_is_charged_to_the_innermost_op_once(tmp_path):
    tr = T.load(_write(tmp_path, _xspace(EVENTS)))
    ops = tr.device_ops["/device:TPU:0"]
    lo, hi = tr.window()
    got = T.scope_seconds(ops, lo, hi)
    # the while op keeps only the time no op of its body ran: 100 - 20 - 30
    assert got[""] == pytest.approx((50 + 10) * 1e-6, abs=1e-12)
    assert got["attn"] == pytest.approx(20e-6, abs=1e-12)
    assert got["spm"] == pytest.approx(30e-6, abs=1e-12)
    assert got["embed"] == pytest.approx(5e-6, abs=1e-12)
    assert sum(got.values()) == pytest.approx(T.busy_seconds(ops, lo, hi))
    assert T.scope_share([ops], "spm", lo, hi) == pytest.approx(
        100.0 * 30 / 115, rel=1e-6)
    assert T.scope_share([ops], "moe", lo, hi) is None


def test_nested_and_partly_overlapping_spans():
    ops = [T.Span("while", 0.0, 10.0, scope="ffn"),
           T.Span("a", 1.0, 4.0, scope="attn"),
           T.Span("b", 2.0, 3.0, scope="spm"),
           T.Span("c", 3.5, 6.0, scope="sample"),
           T.Span("d", 12.0, 13.0)]
    got = T.scope_seconds(ops, 0.5, 12.5)
    assert got == pytest.approx({"ffn": 0.5 + 4.0, "attn": 1.0 + 0.5,
                                 "spm": 1.0, "sample": 2.5, "": 0.5})
    assert sum(got.values()) == pytest.approx(T.busy_seconds(ops, 0.5, 12.5))
    assert T.scope_seconds([], 0.0, 1.0) == {}


SHARES = {"attn_share.train": "attn", "embed_share.prefill": "embed",
          "sample_share.prefill": "sample"}


def _reader(name):
    return spec.load_module(os.path.join(spec.BENCH, "metrics",
                                         f"{name}.py")).read


def _ctx(ops, lo=0.0, hi=200e-6):
    return types.SimpleNamespace(ops=[T.clip(ops, lo, hi)], window=(lo, hi),
                                 tr=T)


@pytest.mark.parametrize("name", sorted(SHARES))
def test_share_readers(name):
    scope = SHARES[name]
    read = _reader(name)
    ops = [T.Span("while", 0.0, 100e-6),
           T.Span("op", 10e-6, 30e-6, scope=scope),
           T.Span("spm_call", 40e-6, 70e-6, scope="spm")]
    assert read(_ctx(ops)) == pytest.approx(20.0)
    unscoped = [T.Span(o.name, o.start, o.end) for o in ops]
    assert read(_ctx(unscoped)) is None
    assert read(_ctx([T.Span("op", 0, 1e-6, scope="moe")])) is None
