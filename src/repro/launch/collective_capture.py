"""Measure photonic-link traffic from compiled HLO collectives.

Closes the ROADMAP loop "cost collectives from measured HLO wire bytes
instead of analytic formulas": the TP×SP×PP prefill/decode cells are
lowered and compiled on a forced-host-device mesh (no device allocation —
the same mechanism as ``dryrun.py``), ``hlo_cost.analyze`` extracts the
per-collective ring-model wire bytes from the SPMD-partitioned module
text, and the totals are packaged as a
:class:`repro.core.interconnect.MeasuredTraffic` that
``PicnicSimulator.run(..., measured_c2c=...)`` consumes as the photonic
C2C traffic term.  The default simulator path stays analytic, so the
calibrated Table II numbers are untouched (measured traffic is opt-in).

Methodology follows Photonic Fabric (arXiv:2507.14000) and LEAP's
balanced-dataflow accounting (arXiv:2509.14781): drive the interconnect
model with the traffic the compiled program actually emits.

CLI (runs in its own process so the host device count can be forced):

  PYTHONPATH=src python -m repro.launch.collective_capture \
      --arch llama3.2-1b --mesh 1x8 --seq 512 --batch 1 --variant picnic
"""
import argparse
import json
import math
import os
import re
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

from repro.jax_setup import force_host_devices
from repro.core.interconnect import MeasuredTraffic

# NOTE: importing this module never touches XLA_FLAGS / jax device state
# (the repo convention, see launch/mesh.py).  The forced host device count
# is applied by main() (CLI), by capture_in_subprocess (child env), or by
# the caller (examples/collective_sweep.py) — always before jax loads.

_DEF_AXES = {2: ("data", "model"), 3: ("pod", "data", "model")}


def parse_mesh(spec: str):
    """"1x8" -> data×model; "2x2x2" -> pod×data×model (sizes per axis)."""
    sizes = tuple(int(s) for s in spec.lower().split("x"))
    if len(sizes) not in _DEF_AXES:
        raise ValueError(f"mesh spec {spec!r}: want 2 (data x model) or "
                         "3 (pod x data x model) factors")
    return sizes, _DEF_AXES[len(sizes)]


def capture_cell(arch: str, *, mode: str = "decode", seq_len: int = 512,
                 batch: int = 1, mesh: str = "1x8",
                 variant: str = "picnic", smoke: bool = False) -> Dict:
    """Lower + compile one (arch, mode, mesh) cell and return a record with
    the per-collective measured wire bytes.

    ``mode``: "decode" (one sharded decode step against a ``seq_len``
    cache), "prefill" (prompt of ``seq_len``), or "train".  ``variant`` is
    a ``dryrun.build_cell`` opt_variant ("picnic" turns on the shard_map
    SP attention / partial-softmax decode paths; "pp" is the GPipe cell
    and needs a 3-factor mesh).  ``smoke`` uses the CPU-sized config.
    """
    from repro.configs import ShapeSpec, get_config, get_smoke_config
    from repro.launch import dryrun, hlo_cost
    from repro.launch.mesh import make_mesh

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    sizes, axes = parse_mesh(mesh)
    m = make_mesh(sizes, axes)
    nchips = m.devices.size
    shape = ShapeSpec(f"{mode}_{seq_len}", seq_len, batch, mode)

    t0 = time.time()
    fn, args = dryrun.build_cell(cfg, shape, m, opt_variant=variant)
    compiled = fn.lower(*args).compile()
    t_compile = time.time() - t0
    parsed = hlo_cost.analyze(compiled.as_text(), nchips)
    xla = compiled.cost_analysis()

    wire_per_chip = parsed.wire_bytes
    return {
        "arch": arch, "mode": mode, "seq_len": seq_len, "batch": batch,
        "mesh": dict(zip(axes, sizes)), "nchips": nchips,
        "variant": variant, "smoke": smoke,
        "compile_s": round(t_compile, 2),
        "collectives": parsed.coll,              # per chip, per step
        "wire_bytes_per_chip": wire_per_chip,
        "wire_bytes_total": wire_per_chip * nchips,
        "flops_per_chip": parsed.flops,
        "xla_flops": float(xla.get("flops", 0.0)),
    }


def to_measured_traffic(prefill_rec: Optional[Dict],
                        decode_rec: Dict) -> MeasuredTraffic:
    """Capture records -> the simulator's photonic traffic term.

    Totals are normalized PER REQUEST (divide by the captured batch) so
    they compose with the simulator's single-stream (b=1) Table II walk:
    decode bytes are per generated token, prefill bytes per prompt.
    """
    dec_per_tok = decode_rec["wire_bytes_total"] / max(decode_rec["batch"], 1)
    pre = 0.0
    if prefill_rec is not None:
        pre = prefill_rec["wire_bytes_total"] / max(prefill_rec["batch"], 1)
    return MeasuredTraffic(
        prefill_bytes=pre,
        decode_bytes_per_token=dec_per_tok,
        per_collective=decode_rec["collectives"],
        n_devices=decode_rec["nchips"],
        source=f"hlo:{decode_rec['mesh']}")


def capture_in_subprocess(arch: str, *, modes: Sequence[str] = ("prefill",
                                                               "decode"),
                          seq_len: int = 512, batch: int = 1,
                          mesh: str = "1x8", variant: str = "picnic",
                          smoke: bool = False, devices: Optional[int] = None,
                          timeout: int = 1200) -> List[Dict]:
    """Run the capture CLI in a fresh process (the forced host device count
    must be set before JAX initializes, which an already-running process —
    e.g. ``benchmarks/run.py`` — cannot do for itself).  ``devices``
    defaults to exactly what the mesh spec needs.  The child is a CPU
    study: it is pinned to the CPU backend, so on an accelerator host it
    never reaches for a chip that this process may hold."""
    if devices is None:
        devices = math.prod(parse_mesh(mesh)[0])
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # inherit the user's XLA flags; only the device-count flag is ours
    inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                      env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (inherited + " " if inherited else "") + \
        f"--xla_force_host_platform_device_count={devices}"
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.collective_capture",
           "--arch", arch, "--modes", ",".join(modes),
           "--seq", str(seq_len), "--batch", str(batch),
           "--mesh", mesh, "--variant", variant, "--json"]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"collective capture failed:\n{r.stderr[-3000:]}")
    return json.loads(r.stdout)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--modes", default="prefill,decode",
                    help="comma list of prefill|decode|train")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--mesh", default="1x8",
                    help='"DxM" (data x model) or "PxDxM" (pod first)')
    ap.add_argument("--variant", default="picnic")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable records on stdout (logs -> "
                         "stderr)")
    args = ap.parse_args()

    # before capture_cell's jax import; an env-set count wins
    force_host_devices(math.prod(parse_mesh(args.mesh)[0]))

    recs = []
    for mode in args.modes.split(","):
        rec = capture_cell(args.arch, mode=mode.strip(), seq_len=args.seq,
                           batch=args.batch, mesh=args.mesh,
                           variant=args.variant, smoke=args.smoke)
        recs.append(rec)
        log = sys.stderr if args.json else sys.stdout
        print(f"[{rec['mode']:7s}] {rec['arch']} mesh={rec['mesh']} "
              f"compile={rec['compile_s']}s wire/chip="
              f"{rec['wire_bytes_per_chip']:.3e}B", file=log, flush=True)
        for op, d in sorted(rec["collectives"].items()):
            print(f"    {op:20s} count={int(d['count']):6d} "
                  f"wire={d['wire_bytes']:.3e}B", file=log, flush=True)
    if args.json:
        print(json.dumps(recs))


if __name__ == "__main__":
    main()
