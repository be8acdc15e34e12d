"""Device time by program scope, and idle gaps named by program spans.

A synthetic trace, written as an ``XSpace`` text proto, gives exact
expectations. ``data/small.xplane.pb`` (``make_trace_fixture.py``)
holds one unscoped jitted matmul per step and only the benchmark's
spans.
``data/scoped.xplane.pb`` (``make_scoped_fixture.py``, recorded on one
TPU v5e chip) holds an elementwise loop under ``mfbf/relax.rung0`` and a
sort under ``batch.reduce``, each step inside a ``repro.executor.step``
span that ends with 20 ms of host sleep inside ``repro.executor.pull``,
and 5 ms of sleep between steps.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import scopes, xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(name):
    from jax.profiler import ProfileData

    path = os.path.join(DATA, name)
    with open(path, "rb") as f:
        data = f.read()
    return ProfileData.from_file(path), data


# (name, tf_op, start, end) of each op on the XLA Ops line, in µs; a
# tf_op starting with "@" is stored by reference to a stat metadata.
OPS = [
    ("%while.1 = while()", "jit(f)/mfbf/while", 1000, 6000),
    ("%fusion.1 = fusion()", "jit(f)/mfbf/while/body/cond/branch_0_fun/"
     "relax.rung0/gather", 1000, 3000),
    ("%fusion.2 = fusion()", "@jit(f)/mfbf/while/body/cond/branch_3_fun/"
     "relax.full_edge/scatter-add", 3000, 5000),
    ("%fusion.3 = fusion()", "jit(f)/batch.reduce/reduce_sum", 6000, 7000),
    ("%copy.1 = copy()", None, 7000, 7500),
    ("%fusion.4 = fusion()", "jit(f)/mfbr/init/gather", 8600, 9900),
]
SPANS = [("bench.window", 0, 10000), ("bench.step", 500, 9000),
         ("repro.executor.step", 600, 8800),
         ("repro.executor.pull", 7600, 8500)]


def _synthetic():
    """Serialized ``XSpace`` of ``OPS`` on one TPU and ``SPANS`` on the
    host."""
    from jax.profiler import ProfileData

    def events(rows):
        return "".join(f"events {{ metadata_id: {i + 1} offset_ps: "
                       f"{s * 10**6} duration_ps: {(e - s) * 10**6} }}\n"
                       for i, (_, s, e) in enumerate(rows))

    meta = []
    for i, (name, op, _, _) in enumerate(OPS):
        stat = ""
        if op and op.startswith("@"):
            stat = f"stats {{ metadata_id: 1 ref_value: {100 + i} }}"
            meta.append(f'stat_metadata {{ key: {100 + i} value {{ id: '
                        f'{100 + i} name: "{op[1:]}" }} }}')
        elif op:
            stat = f'stats {{ metadata_id: 1 str_value: "{op}" }}'
        meta.append(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{name}" {stat} }} }}')
    host_meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: '
                        f'{i + 1} name: "{n}" }} }}\n'
                        for i, (n, _, _) in enumerate(SPANS))
    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{events([(n, s, e) for n, _, s, e in OPS])} }}
  {" ".join(meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0
{events(SPANS)} }}
  {host_meta}
}}
"""
    data = ProfileData.text_proto_to_serialized_xspace(text)
    return ProfileData.from_serialized_xspace(data), data


@pytest.fixture(scope="module")
def synthetic():
    return _synthetic()


def test_tf_ops_by_value_and_by_reference(synthetic):
    _, data = synthetic
    ops = scopes.tf_ops(data)
    assert ops == {name: op.lstrip("@") for name, op, _, _ in OPS if op}


def test_scope_busy_of_the_synthetic_trace(synthetic):
    pd, data = synthetic
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    ms = {k: round(v * 1e3, 9) for k, v in busy.by_path.items()}
    # the while's self time is its span less its two children
    assert ms == {"mfbf": 1.0, "mfbf/relax.rung0": 2.0,
                  "mfbf/relax.full_edge": 2.0, "batch.reduce": 1.0,
                  "mfbr/init": 1.3}
    assert busy.unattributed_s == pytest.approx(0.5e-3)
    assert busy.total_s == pytest.approx(7.8e-3)
    assert busy.under("relax.") == pytest.approx(4e-3)
    assert busy.under("mfbf", "mfbr", "batch.") == pytest.approx(7.3e-3)
    assert busy.by_component["while"] == pytest.approx(5e-3)


def test_gaps_named_by_the_innermost_program_span(synthetic):
    pd, _ = synthetic
    r = scopes.reduce_profile(pd)
    # 7.5-8.6 ms lies as much inside repro.executor.step as inside
    # bench.step and the window, and that span is the shortest of them
    assert r.idle_gaps == [("repro.executor.step", pytest.approx(1.1e-3)),
                           ("bench.window", pytest.approx(1e-3)),
                           ("bench.window", pytest.approx(0.1e-3))]
    assert r.span_busy["repro.executor.step"] == (
        1, pytest.approx((6.5 + 0.2) * 1e-3))
    # the benchmark's own reduction names that gap by its own span
    assert xplane.reduce_profile(pd).idle_gaps[0] == (
        "bench.step", pytest.approx(1.1e-3))
    # and through it the host was mostly in the pull
    split = scopes.gap_split(pd)
    assert [g for g, _ in split] == pytest.approx([1.1e-3, 1e-3, 0.1e-3])
    assert split[0][1] == {"repro.executor.pull": pytest.approx(0.9e-3),
                           "repro.executor.step": pytest.approx(0.2e-3)}
    assert split[1][1] == {"bench.window": pytest.approx(0.5e-3),
                           "bench.step": pytest.approx(0.1e-3),
                           "repro.executor.step": pytest.approx(0.4e-3)}


def test_per_layer_of_the_synthetic_trace(synthetic):
    pd, data = synthetic
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    got = scopes.per_layer(scopes.reduce_profile(pd), busy,
                           {"batches": 1, "frontier_arcs": 3,
                            "arc_slots": 4})
    assert got == {"executor_device_ms": pytest.approx(6.7),
                   "relax_device_ms": pytest.approx(4.0),
                   "full_edge_device_ms": pytest.approx(2.0),
                   "relax_arc_yield": 75.0}


@pytest.fixture(scope="module")
def small():
    return _load("small.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return _load("scoped.xplane.pb")


def test_wire_fields_decode_varints_and_lengths():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32, field 4
    # fixed64
    buf = (b"\x08\xac\x02" + b"\x12\x02ab" + b"\x1d" + b"\x00" * 4
           + b"\x21" + b"\x00" * 8)
    got = [(n, v if isinstance(v, int) else bytes(v))
           for n, v in scopes._fields(memoryview(buf))]
    assert got == [(1, 300), (2, b"ab"), (3, b"\x00" * 4),
                   (4, b"\x00" * 8)]
    with pytest.raises(ValueError):
        list(scopes._fields(memoryview(b"\x0b")))  # group start


def test_tf_op_of_the_recorded_fusion(small):
    _, data = small
    ops = scopes.tf_ops(data)
    fusion = [op for name, op in ops.items() if name.startswith("%fusion ")]
    assert fusion == ["jit(<lambda>)/dot_general:"]


def test_unscoped_trace_has_no_program_scope(small):
    pd, data = small
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    r = xplane.reduce_profile(pd)
    assert busy.total_s == pytest.approx(r.busy_s, rel=1e-9)
    assert busy.by_path == {"(none)": pytest.approx(2 * 11_841e-9)}
    assert busy.under("mfbf", "mfbr", "batch.", "relax.") == 0.0
    assert busy.by_component["dot_general:"] == pytest.approx(2 * 11_841e-9)


def test_program_spans_leave_the_bench_reduction_alone(small):
    pd, _ = small
    a, b = xplane.reduce_profile(pd), scopes.reduce_profile(pd)
    assert a == b


def test_scope_busy_of_the_scoped_trace(scoped):
    pd, data = scoped
    ops = scopes.tf_ops(data)
    assert {op.split("/")[1] for op in ops.values()} == {"mfbf",
                                                         "batch.reduce"}
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, ops, lo, hi)
    assert set(busy.by_path) == {"mfbf/relax.rung0", "batch.reduce"}
    # the elementwise loop dominates the sort
    assert busy.by_path["mfbf/relax.rung0"] > 3 * busy.by_path[
        "batch.reduce"] > 0
    assert busy.under("relax.") == busy.by_path["mfbf/relax.rung0"]
    assert busy.under("relax.full_edge") == 0.0
    assert busy.by_component["relax.rung0"] == pytest.approx(
        busy.by_path["mfbf/relax.rung0"])
    # what carries no tf_op (the copy-start/copy-done of the input)
    assert 0 < busy.unattributed_s < 0.01 * busy.total_s
    assert busy.total_s == pytest.approx(xplane.reduce_profile(pd).busy_s,
                                         rel=1e-6)


def test_gaps_split_by_the_innermost_span(scoped):
    pd, _ = scoped
    split = scopes.gap_split(pd)
    # each step's gap runs from the end of its ops through the 20 ms sleep
    # inside repro.executor.pull and the 5 ms outside the step
    for seconds, parts in split[:3]:
        assert 0.024 < seconds < 0.035
        assert sum(parts.values()) == pytest.approx(seconds)
        assert max(parts, key=parts.get) == "repro.executor.pull"
        assert 0.019 < parts["repro.executor.pull"] < 0.026
        assert 0.003 < parts["bench.window"] < 0.008
    # a gap that straddles two steps lies in no one span, so the
    # benchmark's rule (the span that overlaps it most) names the window
    r = scopes.reduce_profile(pd)
    assert [n for n, _ in r.idle_gaps[:3]] == ["bench.window"] * 3
    assert r.span_busy["repro.executor.step"][0] == 3


def test_per_layer_reads_the_scoped_trace(scoped):
    pd, data = scoped
    r = scopes.reduce_profile(pd)
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    got = scopes.per_layer(r, busy, {"batches": 3, "frontier_arcs": 3,
                                     "arc_slots": 4})
    step = r.span_busy["repro.executor.step"]
    assert step[1] > 0.9 * r.busy_s
    assert got["executor_device_ms"] == pytest.approx(1e3 * step[1] / 3)
    assert got["relax_device_ms"] == pytest.approx(
        1e3 * busy.under("relax.") / 3)
    assert 0 < got["relax_device_ms"] < got["executor_device_ms"]
    assert got["full_edge_device_ms"] == 0.0
    assert got["relax_arc_yield"] == 75.0


def test_per_layer_is_none_without_a_trace_or_counters(small):
    none = dict.fromkeys(("executor_device_ms", "relax_device_ms",
                          "full_edge_device_ms", "relax_arc_yield"))
    assert scopes.per_layer(None, None, {}) == none
    # a trace of a program with neither spans nor scopes, and the
    # counters the exact cell kept before the relax counted arcs
    pd, data = small
    lo, hi = scopes.window(pd)
    busy = scopes.scope_busy(pd, scopes.tf_ops(data), lo, hi)
    assert scopes.per_layer(scopes.reduce_profile(pd), busy,
                            {"batches": 3, "relax_calls": 36}) == none
