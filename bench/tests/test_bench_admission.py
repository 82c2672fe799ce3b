"""The serving loop's admission cap, as a mix states it: a host that
stands still for a few seconds piles up more requests than the loop's
default cap of one tenant's queue (64); under the mix's cap they are
queued and answered late, not refused.  Runs hg38-point's tiny copy on
the CPU with one pump held up."""
import time

import pytest

import tiny
from harness import cell, spec
from harness.targets import ProgramTarget

SEED = 2**31 + 4321
RATE = 40.0              # requests/s of the tiny copy
FREEZE_S = 2.5           # 100 arrivals while the host stands still
FREEZE_AT_S = 0.5        # into the window


def _run_with_a_freeze(monkeypatch, mix):
    pump = ProgramTarget.pump
    frozen = []

    def held_up(self):
        if not frozen and time.perf_counter() - held_up.t0 >= FREEZE_AT_S:
            frozen.append(True)
            time.sleep(FREEZE_S)
        pump(self)
    monkeypatch.setattr(ProgramTarget, "pump", held_up)
    opened = cell.Window.run

    def run(self, seconds):
        held_up.t0 = time.perf_counter()
        return opened(self, seconds)
    monkeypatch.setattr(cell.Window, "run", run)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, "hg38-point")
    out = cell.run("hg38-point", SEED, 4.0, False, require_tpu=False,
                   config_override=tiny.tiny_config(
                       spec.config(bench, wl["config"])),
                   mix_override=mix)
    assert frozen
    return out


def test_point_mix_states_a_cap_above_a_freeze():
    mix = spec.traffic("hg38-point")
    rate = mix["streams"][0]["rate_per_s"]
    assert mix["tenant_queue_cap"] >= 8 * rate        # 8 s of arrivals


@pytest.mark.parametrize("mix_cap", [True, False])
def test_a_host_freeze_is_queued_not_refused(monkeypatch, mix_cap):
    mix = tiny.tiny_mix(spec.traffic("hg38-point"), rate=RATE)
    if not mix_cap:
        del mix["tenant_queue_cap"]       # the loop's default: 64
    out = _run_with_a_freeze(monkeypatch, mix)
    assert out["correct"], out["checks"]
    if mix_cap:
        assert out["failed"] == 0
    else:
        assert out["failed"] > 0
