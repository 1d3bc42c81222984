"""Measured-collectives plumbing (ISSUE 2 tentpole): record -> traffic
conversion and the simulator's measured-vs-analytic C2C flag.

Fast lane: no lowering here (the real capture is exercised by the slow
HLO tests and `benchmarks/run.py distributed`); these tests pin the
contract between capture records, MeasuredTraffic, and the simulator."""
import os

import pytest

from repro.configs import get_smoke_config
from repro.core import MeasuredTraffic, PicnicSimulator
from repro.launch import collective_capture as cc


def _rec(mode, wire_total, batch, coll=None):
    return {"arch": "x", "mode": mode, "seq_len": 512, "batch": batch,
            "mesh": {"data": 1, "model": 8}, "nchips": 8,
            "variant": "picnic", "smoke": True, "compile_s": 0.0,
            "collectives": coll or {}, "wire_bytes_per_chip": wire_total / 8,
            "wire_bytes_total": wire_total, "flops_per_chip": 0.0,
            "xla_flops": 0.0}


def test_parse_mesh():
    assert cc.parse_mesh("2x4") == ((2, 4), ("data", "model"))
    assert cc.parse_mesh("2x2x2") == ((2, 2, 2), ("pod", "data", "model"))
    with pytest.raises(ValueError):
        cc.parse_mesh("8")


def test_subprocess_device_count_follows_mesh(monkeypatch):
    seen = {}

    def fake_run(cmd, **kw):
        seen["flags"] = kw["env"]["XLA_FLAGS"]

        class R:
            returncode = 0
            stdout = "[]"
            stderr = ""
        return R()

    monkeypatch.setattr(cc.subprocess, "run", fake_run)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    cc.capture_in_subprocess("x", mesh="2x8")
    assert seen["flags"] == "--xla_force_host_platform_device_count=16"

    # other inherited flags survive; a stale device count is replaced
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/d "
                       "--xla_force_host_platform_device_count=4")
    cc.capture_in_subprocess("x", mesh="1x8")
    assert seen["flags"] == ("--xla_dump_to=/tmp/d "
                             "--xla_force_host_platform_device_count=8")


@pytest.mark.parametrize("inherited", [None, "tpu", "tpu,cpu"])
def test_subprocess_is_pinned_to_cpu(monkeypatch, inherited):
    # the child is a CPU compile study: on an accelerator host it must not
    # reach for a chip the parent may hold
    seen = {}

    def fake_run(cmd, **kw):
        seen["platforms"] = kw["env"].get("JAX_PLATFORMS")

        class R:
            returncode = 0
            stdout = "[]"
            stderr = ""
        return R()

    monkeypatch.setattr(cc.subprocess, "run", fake_run)
    monkeypatch.setenv("JAX_PLATFORMS", "sentinel")
    if inherited is None:
        monkeypatch.delenv("JAX_PLATFORMS")
    else:
        monkeypatch.setenv("JAX_PLATFORMS", inherited)
    cc.capture_in_subprocess("x", mesh="1x8")
    assert seen["platforms"] == "cpu"
    assert os.environ.get("JAX_PLATFORMS") == inherited   # parent untouched


def test_importing_capture_module_leaves_device_state_alone():
    # repo convention (launch/mesh.py): imports never touch XLA_FLAGS;
    # the fast lane must keep the real single-device CPU view
    import os
    assert "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "")


def test_to_measured_traffic_normalizes_per_request():
    coll = {"all-reduce": {"count": 4.0, "bytes": 10.0, "wire_bytes": 8.0}}
    mt = cc.to_measured_traffic(_rec("prefill", 4000.0, batch=4),
                                _rec("decode", 800.0, batch=4, coll=coll))
    assert mt.prefill_bytes == 1000.0
    assert mt.decode_bytes_per_token == 200.0
    assert mt.per_collective["all-reduce"]["wire_bytes"] == 8.0
    assert mt.n_devices == 8 and mt.source.startswith("hlo")


def test_to_measured_traffic_without_prefill():
    mt = cc.to_measured_traffic(None, _rec("decode", 80.0, batch=1))
    assert mt.prefill_bytes == 0.0
    assert mt.decode_bytes_per_token == 80.0


def test_simulator_measured_c2c_flag():
    cfg = get_smoke_config("llama3.2-1b")
    sim = PicnicSimulator()
    base = sim.run(cfg, 128, 128)
    mt = MeasuredTraffic(prefill_bytes=1e6, decode_bytes_per_token=100.0,
                         source="hlo:test")
    meas = sim.run(cfg, 128, 128, measured_c2c=mt)
    # the flag swaps ONLY the traffic term: timing identical, bytes
    # replaced by prefill + per-token * ctx_out, source recorded
    assert meas.throughput_tps == base.throughput_tps
    assert meas.c2c_bytes_total == int(1e6) + 100 * 128
    assert meas.c2c_source == "hlo:test"
    assert meas.c2c_avg_power_W >= base.c2c_avg_power_W


def test_simulator_default_path_untouched():
    cfg = get_smoke_config("llama3.2-1b")
    sim = PicnicSimulator()
    a, b = sim.run(cfg, 128, 128), sim.run(cfg, 128, 128)
    assert a == b
    assert a.c2c_source == "analytic"
