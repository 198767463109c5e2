"""CPU rehearsal of ``chip_smoke.py``: its per-network check, run at 32x32
on the XLA path through the same front door, HTTP round trip, bit-match,
reference and "nothing hid the device" checks the chip run makes.  The
platform check stays in ``main()``, so the script itself refuses a CPU."""
import importlib.util
import pathlib

import pytest

from repro.frontend import FrontDoor, LocalBackend, ServerThread
from repro.frontend.worker import build_server

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


def test_checks_pass_for_every_network_at_32px():
    server = build_server(smoke.make_spec(smoke.NETS, 32))
    with ServerThread(FrontDoor(LocalBackend(server))) as door:
        for i, name in enumerate(smoke.NETS):
            out, rows = smoke.serve_and_check(
                server, door.port, name, smoke.images(10, 32, 1 + i))
            assert rows.shape == (10, 1000)
            assert out["max_abs_diff_vs_batch1"] == 0.0
            assert out["batches"] < out["requests"]
    smoke.check_nothing_hidden(server)


def test_nothing_hidden_check_sees_a_failover():
    server = build_server({"networks": [{"kind": "fire", "name": "tiny"}]})
    server.shutdown()
    smoke.check_nothing_hidden(server)
    server.metrics.count("failovers")
    with pytest.raises(smoke.SmokeFailure, match="failovers"):
        smoke.check_nothing_hidden(server)


def test_pallas_calls_reads_tpu_custom_calls():
    hlo = "\n".join([
        '  %int8_gemm.3 = f32[8,128]{1,0} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", backend_config={}',
        '  %fused_chain = f32[8,28,28,24]{3,2,1,0} custom-call(%c), '
        'custom_call_target="tpu_custom_call"',
        '  %int8_gemm.4 = f32[8,128]{1,0} custom-call(%d), '
        'custom_call_target="tpu_custom_call"',
        '  %dot.1 = f32[8,8]{1,0} dot(%e, %f)'])
    assert smoke.pallas_calls(hlo) == {"int8_gemm": 2, "fused_chain": 1}


def test_main_refuses_a_cpu(capsys):
    assert smoke.main([]) == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs a TPU" in captured.err
