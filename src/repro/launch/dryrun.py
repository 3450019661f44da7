import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

Per cell we record:
  * compiled.memory_analysis()  — per-device bytes (does it fit HBM?)
  * compiled.cost_analysis()    — per-device FLOPs / bytes accessed
  * collective bytes by opcode  — parsed from the partitioned HLO text
and persist JSON to results/dryrun/ for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--subprocess]
"""
import argparse
import json
import re
import subprocess
import sys
import time
import traceback

import jax


RESULTS_DIR = os.environ.get(
    "REPRO_DRYRUN_DIR",
    os.path.join(os.path.dirname(__file__), "..", "..", "..",
                 "results", "dryrun"))

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str):
    """Sum result-buffer sizes of every collective op in the partitioned HLO
    (per-device bytes).  Returns {opcode: bytes}."""
    out = {c: 0 for c in _COLLECTIVES}
    count = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for c in _COLLECTIVES:
            # match the op name, e.g. "%ag = bf16[2,16] all-gather(...)"
            if f" {c}(" in stripped or f" {c}-start(" in stripped:
                m = _SHAPE_RE.search(stripped.split("=", 1)[-1])
                if m:
                    dt, dims = m.group(1), m.group(2)
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    out[c] += n * _DTYPE_BYTES.get(dt, 4)
                    count[c] += 1
                break
    return out, count


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose=True):
    from repro.configs import get_arch, shapes_for
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    cfg = get_arch(arch)
    shape = {s.name: s for s in shapes_for(cfg)}[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape, mesh)

    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "status": "ok"}
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec["memory_analysis"] = {
        k: int(getattr(mem, k, 0)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")}
    per_dev = (rec["memory_analysis"]["argument_size_in_bytes"]
               + rec["memory_analysis"]["temp_size_in_bytes"])
    rec["bytes_per_device"] = per_dev
    # raw XLA numbers (while bodies counted ONCE — kept for reference only)
    rec["xla_flops_raw"] = float(cost.get("flops", 0.0))
    rec["xla_bytes_raw"] = float(cost.get("bytes accessed", 0.0))
    # loop-aware analysis (the roofline source; see distributed/hlo_analysis)
    t2 = time.time()
    from repro.distributed.hlo_analysis import analyze
    la = analyze(compiled.as_text())
    rec["flops_per_device"] = la["flops"]
    rec["hbm_bytes_per_device"] = la["bytes_hbm"]
    rec["collective_bytes"] = la["collectives"]
    rec["collective_bytes_total"] = la["collective_bytes_total"]
    rec["hlo_parse_s"] = round(time.time() - t2, 1)

    if verbose:
        print(f"== {arch} x {shape_name} [{rec['mesh']}] "
              f"lower {rec['lower_s']}s compile {rec['compile_s']}s")
        print(f"   memory_analysis: {rec['memory_analysis']}")
        print(f"   flops/device={rec['flops_per_device']:.3e} "
              f"hbm_bytes/device={rec['hbm_bytes_per_device']:.3e}")
        print(f"   collectives: { {k: f'{v:.2e}' for k, v in la['collectives'].items()} }")
    return rec


def save(rec):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    key = f"{rec['arch']}__{rec['shape']}__{rec['mesh'].replace('x','_')}"
    with open(os.path.join(RESULTS_DIR, key + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def cell_done(arch, shape, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    key = f"{arch}__{shape}__{mesh.replace('x','_')}"
    path = os.path.join(RESULTS_DIR, key + ".json")
    if not os.path.exists(path):
        return False
    with open(path) as f:
        return json.load(f).get("status") == "ok"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if not args.all:
        rec = run_cell(args.arch, args.shape, args.multi_pod)
        save(rec)
        return

    # --all: spawn one subprocess per cell (isolated XLA state, resumable)
    from repro.configs import ASSIGNED, get_arch, shapes_for
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    todo = []
    for name in ASSIGNED:
        for shape in shapes_for(get_arch(name)):
            for mp in meshes:
                if args.force or not cell_done(name, shape.name, mp):
                    todo.append((name, shape.name, mp))
    print(f"{len(todo)} cells to run")
    for i, (name, sname, mp) in enumerate(todo):
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", name, "--shape", sname]
        if mp:
            cmd.append("--multi-pod")
        print(f"[{i+1}/{len(todo)}] {' '.join(cmd[3:])}", flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True)
        if r.returncode != 0:
            rec = {"arch": name, "shape": sname,
                   "mesh": "2x16x16" if mp else "16x16", "status": "fail",
                   "error": (r.stderr or "")[-2000:]}
            save(rec)
            print(f"   FAIL ({time.time()-t0:.0f}s): {r.stderr[-400:]}")
        else:
            print(f"   ok ({time.time()-t0:.0f}s)")


if __name__ == "__main__":
    main()
