"""Multi-pod dry-run walk-through for ONE cell: lower + compile yi-34b
decode_32k on the 512-chip mesh, print the memory/cost analysis and the
derived roofline terms — exactly what the full sweep does for all 40 cells.

  PYTHONPATH=src python examples/multipod_dryrun.py [arch] [shape]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.jax_setup import force_host_devices
force_host_devices(512)   # CPU backend, 512 host devices; before any jax import

from repro.launch.dryrun import run_cell

arch = sys.argv[1] if len(sys.argv) > 1 else "yi-34b"
shape = sys.argv[2] if len(sys.argv) > 2 else "decode_32k"

for mesh, variant in [("pod2", "baseline"), ("pod2", "picnic")]:
    rec = run_cell(arch, shape, mesh, variant, save=False)
    print(f"\n=== {rec['cell']} [{variant}] -> {rec['status']} ===")
    if rec["status"] != "ok":
        print(rec.get("reason") or rec.get("error"))
        continue
    m = rec["memory"]
    print(f"chips: {rec['nchips']}  compile: {rec['compile_s']}s")
    print(f"per-chip residency (args): {m['argument_bytes']/1e9:.2f} GB")
    print(f"flops/chip: {rec['flops_per_chip']:.3e} "
          f"(useful fraction {rec['useful_flop_frac']:.2f})")
    print("roofline terms (s):",
          {k: round(v, 5) for k, v in rec["roofline"].items()},
          "->", rec["dominant"])
    print("collectives:", {k: (int(v['count']), f"{v['wire_bytes']:.2e}B")
                           for k, v in rec["collectives"].items()})
