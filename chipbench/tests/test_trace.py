"""The trace reduction on a small trace recorded on one TPU v5e: a slice of
the traced window of a ``shfl.saturate`` run, as ``trace.extract`` keeps
it (``data/shfl_saturate_trace.json.gz``)."""
import gzip
import json
import statistics

import pytest

from conftest import ROOT
from chipbench.trace import Trace, merge

DATA = ROOT / "chipbench" / "tests" / "data" / "shfl_saturate_trace.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_merge_is_the_union():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == [
        (0, 3), (5, 8), (10, 11)]


def test_busy_and_idle_add_up_to_the_window(recorded):
    t = Trace(recorded)
    (plane,) = recorded
    a, b = recorded[plane]["window"]
    # the union, worked out by marking every microsecond a device op covers
    covered = set()
    for _, s, d in recorded[plane]["XLA Ops"]:
        lo, hi = max(a, s), min(b, s + d)
        covered.update(range(int(lo // 1000), int(-(-hi // 1000))))
    assert t.busy_s() == pytest.approx(len(covered) * 1e-6, rel=0.05)
    assert 0 < t.idle_share() < 1
    gaps = t.idle_gaps(top=10 ** 6)
    assert sum(gaps) == pytest.approx(t.window_s - t.busy_s(), rel=1e-9)


def test_kernel_and_program_times(recorded):
    t = Trace(recorded)
    (plane,) = recorded
    a, b = recorded[plane]["window"]
    ops = recorded[plane]["XLA Ops"]
    for kernel in ("int8_gemm", "fused_chain"):
        want = sum(d for n, s, d in ops if a <= s < b and (
            n.startswith(kernel + "."))) * 1e-9
        assert want > 0
        assert t.kernel_s(kernel) == pytest.approx(want)
    runs = t.module_s("jit_run")
    assert runs and all(r < t.window_s for r in runs)
    # a program's run covers its ops: the kernels fit inside the runs
    assert t.kernel_s("int8_gemm") < sum(runs)
    assert statistics.median(runs) > 0


def test_breakdown(recorded):
    out = Trace(recorded).breakdown()
    assert 0 < len(out["device_ops"]) <= 10
    secs = [s for _, s in out["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all(name == "unattributed" for name, _ in out["idle_gaps"])


def test_no_device_planes_read_as_empty():
    t = Trace({})
    assert t.busy_s() == t.window_s == 0.0
    assert t.idle_share() is None and t.kernel_s("int8_gemm") == 0


def test_the_harness_runs_the_marker_the_reduction_looks_for():
    from chipbench import run, trace
    assert run.chipbench_window.__name__ == trace.MARKER


@pytest.mark.parametrize("name", ["idle_share.saturate",
                                  "idle_share.stream"])
def test_split_metrics_share_one_reader(recorded, name):
    """A metric split by its cells with no file of its own is read by
    ``metrics/<quantity>.py``."""
    from chipbench.run import Run, load_reader
    t = Trace(recorded)
    run = Run({}, t, {}, 1, {})
    assert load_reader(name)(run) == pytest.approx(100 * t.idle_share())
