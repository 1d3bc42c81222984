"""Mesh-shape sweep of MEASURED collective wire bytes (ISSUE 2 tentpole).

For each TP×SP mesh shape (and one PP mesh) this lowers + compiles the
sharded prefill/decode cells on forced host devices, extracts the
per-collective wire bytes from the compiled HLO
(`launch/collective_capture.py`), and feeds the decode traffic into the
PICNIC simulator as the measured photonic C2C term — printed next to the
default analytic estimate.  Smoke-sized configs by default; pass --full
for the real arch (slower lowering, paper-scale bytes).

  PYTHONPATH=src python examples/collective_sweep.py [arch] [--full]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.jax_setup import force_host_devices
force_host_devices(8)   # CPU backend, 8 host devices; before any jax import

from repro.configs import get_config, get_smoke_config
from repro.core import PicnicSimulator
from repro.launch.collective_capture import capture_cell, to_measured_traffic

arch = next((a for a in sys.argv[1:] if not a.startswith("-")),
            "llama3.2-1b")
smoke = "--full" not in sys.argv

print(f"=== {arch} ({'smoke' if smoke else 'full'} config), seq 512 ===")
captures = {}
for mesh in ("1x8", "2x4", "4x2"):
    row = {}
    for mode in ("prefill", "decode"):
        rec = capture_cell(arch, mode=mode, seq_len=512,
                           batch=int(mesh.split("x")[0]), mesh=mesh,
                           variant="picnic", smoke=smoke)
        row[mode] = rec
        colls = {op: f"{d['wire_bytes']:.2e}B"
                 for op, d in sorted(rec["collectives"].items())}
        print(f"mesh {mesh} (data x model) {mode:7s} "
              f"wire/chip={rec['wire_bytes_per_chip']:.3e}B  {colls}")
    captures[mesh] = row

# GPipe cell: pod x data x model, stage axis manual inside the shard_map
try:
    # batch 16: 8 microbatches (build_cell's pp schedule) x 2-way DP
    rec = capture_cell(arch, mode="train", seq_len=128, batch=16,
                       mesh="2x2x2", variant="pp", smoke=smoke)
    colls = {op: f"{d['wire_bytes']:.2e}B"
             for op, d in sorted(rec["collectives"].items())}
    print(f"mesh 2x2x2 (pod x data x model) pp-train "
          f"wire/chip={rec['wire_bytes_per_chip']:.3e}B  {colls}")
except Exception as e:  # noqa: BLE001 — the sweep reports, never aborts
    print(f"mesh 2x2x2 pp-train failed: {type(e).__name__}: {e}")

# feed the 1x8 decode traffic into the photonic cost model
cfg = get_smoke_config(arch) if smoke else get_config(arch)
mt = to_measured_traffic(captures["1x8"]["prefill"],
                         captures["1x8"]["decode"])
sim = PicnicSimulator()
r_an = sim.run(cfg, 512, 512)
r_me = sim.run(cfg, 512, 512, measured_c2c=mt)
print(f"\nsimulator C2C term    analytic: {r_an.c2c_bytes_total:.3e}B "
      f"-> {1e3 * r_an.c2c_avg_power_W:.3f} mW")
print(f"                      measured: {r_me.c2c_bytes_total:.3e}B "
      f"-> {1e3 * r_me.c2c_avg_power_W:.3f} mW "
      f"(source {r_me.c2c_source})")
print("throughput unchanged:", r_an.throughput_tps == r_me.throughput_tps)
