"""The trace reducer: exact arithmetic on a hand-written XSpace, and the
same code on the small trace recorded on a v5e that is kept beside the
benchmark (CPU only: a trace is a file)."""

import os
import re

import pytest

from benchmark.harness import layer, spec, trace

RECORDED = os.path.join(spec.ROOT, "benchmark", "testdata",
                        "sweeps_v5e.xplane.pb.gz")


def _events(meta, spans_us):
    return "".join(
        f"events {{ metadata_id: {meta[name]} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int((e - s) * 1e6)} }}\n" for name, s, e in spans_us)


def _plane(name, lines):
    names = sorted({n for _ln, evs in lines for n, _s, _e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = "".join(
        f'lines {{ name: "{ln}" timestamp_ns: 0\n{_events(meta, evs)}}}\n'
        for ln, evs in lines)
    metas = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in meta.items())
    return f'planes {{ name: "{name}"\n{body}{metas}}}\n'


# times in microseconds
XSPACE = _plane("/device:TPU:0", [
    ("XLA Modules", [("jit__counting_sweep(111)", 1000, 2000),
                     ("jit__counting_sweep(111)", 5000, 6000),
                     ("jit_convert(5)", 9000, 9100),
                     ("jit_convert(5)", 13000, 13100)]),
    ("XLA Ops", [("fusion.1", 1000, 1400), ("while.2", 1400, 2000),
                 ("fusion.3", 1500, 1700),  # nested in while.2
                 ("fusion.1", 5000, 5400), ("while.2", 5400, 6000),
                 ("copy.1", 9000, 9100), ("copy.1", 13000, 13100)]),
]) + _plane("/host:CPU", [
    ("python3", [("bench:sync", 1900, 4800),
                 ("PjitFunction(_counting_sweep)", 6100, 6500),
                 ("DevicePut", 6150, 6200)]),  # nested: never outweighs
    ("python3", [("bench:pump", 8990, 9000)]),
])


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_text_proto(XSPACE), window_s=0.02)


def test_busy_time_is_the_union_of_device_op_intervals(summary):
    assert summary.chips == 1
    # 1000..2000, 5000..6000, 9000..9100, 13000..13100: nested ops once
    assert summary.busy_s == pytest.approx(2200e-6)
    assert summary.window_s == 0.02


def test_program_time_is_found_by_name_without_the_fingerprint(summary):
    assert summary.programs["jit__counting_sweep"] == [2, pytest.approx(2e-3)]
    assert summary.program_time(re.compile("counting_sweep")) == (
        2, pytest.approx(2e-3))
    assert summary.program_time(re.compile("absent")) == (0, 0.0)
    ctx = {"trace": summary, "counters": {}, "samples": {}}
    d = {"kind": "device_op_time", "match": "counting_sweep", "scale": 1e6}
    assert layer.evaluate(d, ctx) == pytest.approx(1000.0)  # us per sweep
    assert layer.evaluate(dict(d, stat="count", scale=1), ctx) == 2.0
    assert layer.evaluate(dict(d, match="absent"), ctx) is None


def test_device_ops_are_ranked_by_total_time(summary):
    assert summary.device_ops[0] == ("while.2", pytest.approx(1200e-6))
    assert summary.device_ops[1] == ("fusion.1", pytest.approx(800e-6))
    assert len(summary.breakdown()["device_ops"]) <= 10


def test_idle_gaps_are_attributed_to_what_the_host_was_doing(summary):
    gaps = dict(summary.idle_gaps)
    # 2000..5000 under the harness's sync span; 6000..9000 with a traced
    # dispatch in it; 9100..13000 with nothing traced
    assert gaps == {
        "span sync": pytest.approx(3000e-6),
        "host PjitFunction(_counting_sweep)": pytest.approx(3000e-6),
        "host: nothing traced (Python)": pytest.approx(3900e-6),
    }


def test_merge_is_the_union_as_disjoint_intervals():
    assert trace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_a_trace_with_no_device_plane_reads_as_not_busy():
    from jax.profiler import ProfileData

    host_only = _plane("/host:CPU", [("python3", [("bench:pump", 0, 10)])])
    s = trace.reduce(ProfileData.from_text_proto(host_only), window_s=1.0)
    assert (s.chips, s.busy_s, s.idle_gaps) == (0, 0.0, [])


def test_a_reader_is_found_by_path_and_may_find_nothing(summary, tmp_path):
    path = tmp_path / "sweeps_in_trace.py"
    path.write_text(
        "import re\n\n\n"
        "def read(ctx):\n"
        "    if ctx.get('trace') is None:\n"
        "        return None\n"
        "    return float(ctx['trace'].program_time(re.compile('sweep'))[0])\n")
    d = {"kind": "reader", "path": str(path)}
    assert layer.evaluate(d, {"trace": summary}) == 2.0
    assert layer.evaluate(d, {"trace": None}) is None


def test_the_trace_recorded_on_a_v5e_reduces_as_the_docstring_says():
    """Three rounds of two sweeps (buckets (64,256,16,1,16) and
    (128,1024,16,1,32)) under the harness's spans, recorded on one v5e chip
    by PR 23: the names the reducer relies on are the chip's own."""
    import gzip

    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    assert "/device:TPU:0" in {p.name for p in profile.planes}
    s = trace.reduce(profile, window_s=0.05)
    assert s.chips == 1
    assert list(s.programs) == ["jit__counting_sweep"]
    executions, seconds = s.programs["jit__counting_sweep"]
    assert executions == 6
    # each sweep took some tens of microseconds on the device
    assert 10e-6 < seconds / executions < 1e-3
    # the device was busy only inside those executions
    assert 0 < s.busy_s <= seconds
    assert s.device_ops and s.device_ops[0][1] <= s.busy_s
    # the gaps between sweeps lie under the harness's spans
    assert s.idle_gaps and all(n.startswith("span ") for n, _s in s.idle_gaps)
    assert sum(sec for _n, sec in s.idle_gaps) < 0.05
