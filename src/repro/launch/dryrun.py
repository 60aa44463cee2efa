import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh)
cell — proof that the distribution config is coherent without hardware.

For each cell this prints/records:
  * compiled.memory_analysis()  — per-device bytes (does it fit?)
  * compiled.cost_analysis()    — FLOPs / bytes for §Roofline
  * collective bytes parsed from the optimized HLO — the §Roofline third term

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both \
      --out results/dryrun.json
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any

import jax
import numpy as np

from repro.configs import ASSIGNED, SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.distributed.sharding import PROFILES, use_sharding
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh

# ---------------------------------------------------------------------------
# Cell enumeration + skip table (documented in DESIGN.md §6)
# ---------------------------------------------------------------------------

LONG_OK = {"mamba2-780m", "zamba2-7b", "deepseek-v3-671b"}
SKIPS: dict[tuple[str, str], str] = {
    ("whisper-large-v3", "long_500k"): "enc-dec, full attention decoder",
    ("gemma2-27b", "long_500k"): "global layers are full attention",
    ("gemma3-27b", "long_500k"): "global layers are full attention",
    ("qwen3-0.6b", "long_500k"): "pure full attention",
    ("qwen1.5-110b", "long_500k"): "pure full attention",
    ("dbrx-132b", "long_500k"): "pure full attention",
    ("qwen2-vl-7b", "long_500k"): "pure full attention",
}


def enumerate_cells() -> list[tuple[str, str, str | None]]:
    """[(arch, shape, skip_reason|None)] — 40 cells total."""
    out = []
    for arch in ASSIGNED:
        for shape in SHAPES:
            out.append((arch, shape, SKIPS.get((arch, shape))))
    return out


def cell_config(arch: str, shape: str):
    """Arch config for a cell; deepseek long/ess cells use the paper's
    V3.2-Exp + ESS variant (DSA makes 500k sub-quadratic)."""
    cfg = get_config(arch)
    cell = SHAPES[shape]
    if arch == "deepseek-v3-671b" and shape == "long_500k":
        cfg = get_config("deepseek-v32-exp-ess")
    return cfg, cell


# ---------------------------------------------------------------------------
# HLO collective accounting (§Roofline collective term)
# ---------------------------------------------------------------------------

_COLL_RE = re.compile(
    r"(\w[\w\.\-]*) = (\S+?) (all-gather|all-reduce|reduce-scatter|"
    r"all-to-all|collective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
                "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1, "s64": 8,
                "u64": 8, "f64": 8, "s16": 2, "u16": 2}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Sum output-shape bytes of every collective op in the optimized HLO."""
    per_kind: dict[str, int] = {}
    count: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        b = _shape_bytes(m.group(2))
        per_kind[kind] = per_kind.get(kind, 0) + b
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "count_by_kind": count,
            "total_bytes": sum(per_kind.values())}


# ---------------------------------------------------------------------------
# One cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: str, *, multi_pod: bool,
             verbose: bool = True, profile: str | None = None
             ) -> dict[str, Any]:
    cfg, cell = cell_config(arch, shape)
    skip = SKIPS.get((arch, shape))
    if skip:
        return {"arch": arch, "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": skip}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    seq_data = cell.global_batch == 1
    prof = profile or cfg.sharding_profile
    if (profile is None and cell.kind == "decode"
            and cfg.sharding_profile == "2d" and not cfg.ess.enabled):
        # §Perf: weights-stationary decode (10-17x fewer collective bytes);
        # reproduce the paper-faithful baseline with --sharding-profile 2d
        prof = "2d_ws"
    rules = PROFILES[prof](multi_pod, seq_data=seq_data)
    rec: dict[str, Any] = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if multi_pod else "16x16",
                           "profile": prof}
    try:
        with use_sharding(mesh, rules):
            specs = ST.input_specs(cfg, cell)
            params, opt = ST.abstract_state(cfg, cell)
            step = ST.make_step(cfg, cell)
            shd_of = lambda tree: jax.tree.map(lambda x: x.sharding, tree)
            ctx = None
            from repro.distributed import sharding as _shd
            ctx = _shd.current()
            if cell.kind == "train":
                # donate params+opt (in-place update); outputs keep the
                # input shardings so aliasing is exact
                out_sh = (shd_of(params), shd_of(opt),
                          {"loss": ctx.sharding(), "grad_norm": ctx.sharding(),
                           "lr": ctx.sharding()})
                lowered = jax.jit(step, donate_argnums=(0, 1),
                                  out_shardings=out_sh).lower(
                    params, opt, specs)
            else:
                # decode: donate the batch (caches alias in place); output
                # shardings stay inferred — explicit out_shardings with
                # mixed memory kinds trips an SPMD RET_CHECK in this XLA
                lowered = jax.jit(step).lower(params, specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            "collectives": coll,
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "host_argument_bytes": ma.host_argument_size_in_bytes,
                "host_temp_bytes": ma.host_temp_size_in_bytes,
            },
        })
        if verbose:
            print(f"[ok] {arch} × {shape} × {rec['mesh']} "
                  f"(lower {t_lower:.0f}s compile {t_compile:.0f}s) "
                  f"flops={rec['flops']:.3e} "
                  f"coll={coll['total_bytes']:.3e}B "
                  f"temp/dev={ma.temp_size_in_bytes/2**30:.2f}GiB")
            print(f"     memory_analysis: {ma}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[ERR] {arch} × {shape} × {rec['mesh']}: {e}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["off", "on", "both"],
                    default="off")
    ap.add_argument("--out", default=None)
    ap.add_argument("--ess", action="store_true",
                    help="use the ESS-enabled deepseek variant for decode")
    ap.add_argument("--sharding-profile", default=None,
                    help="override the arch sharding profile (perf variants)")
    args = ap.parse_args(argv)

    meshes = {"off": [False], "on": [True], "both": [False, True]}[
        args.multi_pod]
    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a, s, _ in enumerate_cells()]
    else:
        archs = [args.arch] if args.arch else ASSIGNED
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(a, s) for a in archs for s in shapes]

    results = []
    for arch, shape in cells:
        a = arch
        if args.ess and arch == "deepseek-v3-671b":
            a = "deepseek-v32-exp-ess"
        for mp in meshes:
            results.append(run_cell(a, shape, multi_pod=mp,
                                    profile=args.sharding_profile))

    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run: {ok} ok, {sk} skipped, {err} errors "
          f"/ {len(results)} cells ===")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    return 1 if err else 0


if __name__ == "__main__":
    sys.exit(main())
