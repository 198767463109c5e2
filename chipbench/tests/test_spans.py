"""The span reading (``chipbench/spans.py``): the device clock's offset
from the markers' host brackets, checked on a window recorded on one TPU
v5e (``data/shfl_saturate_spans.json.gz``, written by ``spanrun.py
--dump``); idle gaps named by the span that covers them, on a synthetic
window; and the span metrics through a CPU rehearsal of ``spanrun.py``."""
import gzip
import json

import pytest

from conftest import ROOT, tiny_bench
from chipbench.spans import Spans, offset, offset_bounds
from chipbench.trace import MARKER, Trace

DATA = ROOT / "chipbench" / "tests" / "data" / "shfl_saturate_spans.json.gz"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_markers_agree_on_a_small_offset(recorded):
    """Each marker's bracket allows a range of offsets; the two ranges
    meet, and the offset is known to within half a millisecond."""
    (plane,) = recorded["planes"].values()
    (lo0, hi0), (lo1, hi1) = offset_bounds(plane, recorded["brackets"])
    assert lo0 <= hi0 and lo1 <= hi1
    assert max(lo0, lo1) <= min(hi0, hi1)
    off, half = offset(plane, recorded["brackets"])
    assert 0 <= half <= 0.5e6
    # the spans fall inside the window the markers put on the host clock
    spans = Spans(recorded["spans"], recorded["brackets"])
    a, b = spans.window
    assert a < b and spans.inside({"server.batch"})


def test_recorded_gaps_are_named(recorded):
    """At least nine tenths of the length of the ten longest idle gaps is
    named by a span, and the span metrics read from the window."""
    spans = Spans(recorded["spans"], recorded["brackets"])
    gaps = spans.label_gaps(Trace(recorded["planes"]))
    assert len(gaps) == 10
    named = sum(g for label, g in gaps if label != "unattributed")
    assert named >= 0.9 * sum(g for _, g in gaps)
    assert spans.door_ms() > 0 and spans.batch_host_ms() > 0
    assert spans.queue_ms() > 0


def _synthetic(records, gap=(4_000, 6_000), off=1_000_000):
    """A device busy over [0, 10 us] but for ``gap`` (device ns), its
    markers run at 0 and 10 us, and the host clock ``off`` ns ahead; with
    ``records`` on the host clock."""
    marks = [[f"jit_{MARKER}(1)", -100.0, 100.0],
             [f"jit_{MARKER}(1)", 10_000.0, 100.0]]
    ops = [["op", 0.0, float(gap[0])],
           ["op", float(gap[1]), 10_000.0 - gap[1]]]
    plane = {"window": [0.0, 10_000.0], "XLA Modules": marks,
             "XLA Ops": ops}
    brackets = [(off - 150, off + 20), (off + 9_990, off + 10_150)]
    return Trace({"/device:TPU:0": plane}), Spans(records, brackets)


def _rec(name, t0, t1, replica=-1, off=1_000_000):
    return (name, off + t0, off + t1, 1, 1, 7, replica)


@pytest.mark.parametrize("records,label", [
    # the drain thread waited for the device's result over the whole gap
    ([_rec("server.device_wait", 3_500, 6_500)], "server.device_wait"),
    # it padded the next batch over most of it, and then dispatched it
    ([_rec("server.pad", 3_900, 5_800), _rec("server.dispatch", 5_800,
                                             6_100)], "server.pad"),
    # a dispatch on a replica carries the replica
    ([_rec("server.dispatch", 4_000, 6_000, replica=2)],
     "server.dispatch@r2"),
    # it waited for requests while the door read a body
    ([_rec("batcher.wait", 3_000, 7_000), _rec("door.read", 3_000, 5_500),
      _rec("door.decode", 5_500, 5_700)], "batcher.wait<door.read"),
    # it waited for requests, and the door had hardly any to read
    ([_rec("batcher.wait", 3_000, 7_000), _rec("door.read", 5_600, 6_200)],
     "batcher.wait"),
    # the drain thread's spans cover less than half of it
    ([_rec("server.debatch", 5_200, 5_800), _rec("door.read", 3_000,
                                                 7_000)], "unattributed"),
])
def test_a_gap_is_named_by_the_span_over_it(records, label):
    trace, spans = _synthetic(records)
    off, half = spans.offsets(trace)["/device:TPU:0"]
    assert off == pytest.approx(1_000_000, abs=half) and half <= 60
    (gap,) = spans.label_gaps(trace)
    assert gap == [label, pytest.approx(2e-6)]


def test_a_replicas_gap_names_its_replica():
    trace, spans = _synthetic([_rec("server.device_wait", 4_000, 6_000,
                                    replica=0)])
    (gap,) = spans.label_gaps(trace, {"/device:TPU:0": 3})
    assert gap[0] == "r3: server.device_wait@r0"


@pytest.mark.parametrize("cell,names", [
    ("mbv2.stream", {"door_ms.stream", "queue_ms.stream"}),
    ("shfl.saturate", {"door_ms.saturate", "batch_host_ms.saturate"}),
])
def test_cpu_rehearsal_prints_the_span_metrics(tmp_path, monkeypatch,
                                               capsys, cell, names):
    """The span metrics need no device plane: a traced CPU rehearsal of
    ``spanrun.py`` prints each of its cell's."""
    import jax

    import repro.core.executor as executor
    from chipbench import run, spanrun

    monkeypatch.setattr(executor, "_default_use_pallas", lambda: True)
    peaks = json.loads((ROOT / "chipbench" / "peaks.json").read_text())
    monkeypatch.setattr(run, "peak_of", lambda kind: peaks["TPU v5 lite"])
    rc = spanrun.main(["--workload", cell, "--seed", str(2**31 + 13),
                       "--seconds", "2", "--trace", "1"],
                      bench_path=tiny_bench(tmp_path, 64),
                      require=lambda chips: jax.devices()[:chips])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True, line["checks"]
    span_metrics = {m["name"] for m in spanrun.SPAN_METRICS}
    assert set(line["metrics"]) & span_metrics == names
    assert all(line["metrics"][n]["value"] > 0 for n in names)
