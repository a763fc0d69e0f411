import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this script:
  1. builds the production mesh (16x16 single-pod, 2x16x16 multi-pod),
  2. lowers the REAL step function (full train step incl. optimizer, or
     prefill/decode with KV cache) against ShapeDtypeStruct inputs with the
     family sharding rules — no host allocation ever happens,
  3. compiles, printing memory_analysis() (proves the per-device footprint
     fits a 16 GiB v5e) and cost_analysis() (FLOPs/bytes for §Roofline),
  4. parses the post-SPMD HLO for collective ops and estimates
     bytes-on-wire per device (all-reduce counted 2x for the ring),
     multiplying collectives that live inside the layer-stack while-loop by
     the scan trip count,
  5. appends one JSON record per cell to the results file.

Usage:
  python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import re
import sys
import time

import numpy as np  # noqa: E402
import jax  # noqa: E402

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _result_bytes(line: str) -> int:
    """Total bytes of the result shape(s) on an HLO op line."""
    lhs = line.split("=", 1)[0] if "=" in line else line
    # result shape appears right after '=' on the rhs
    rhs = line.split("=", 1)[1] if "=" in line else line
    m = _SHAPE_RE.findall(rhs.split("(", 1)[0])
    total = 0
    for dt, dims in m:
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, loop_multiplier: int) -> dict:
    """Sum estimated bytes-on-wire per device by collective type.

    Ops inside while-loop body computations are multiplied by
    ``loop_multiplier`` (the layer-stack scan length) — HLO shows loop
    bodies once but they execute every iteration.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    current_mult = 1
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if line.startswith(("ENTRY", "%fused", "while_body", "body",
                            "%while_body", "region_")) or line.endswith("{"):
            name = line.split(" ")[0].lstrip("%")
            in_loop = ("while" in name or "body" in name or
                       re.match(r"region_\d+", name) is not None)
            current_mult = loop_multiplier if in_loop else 1
        for coll in _COLLECTIVES:
            if f" {coll}(" in line or f"{coll}-start(" in line:
                nbytes = _result_bytes(line)
                factor = 2.0 if coll == "all-reduce" else 1.0
                out[coll] += nbytes * factor * current_mult
                counts[coll] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": float(sum(out.values()))}


def loop_multiplier_for(arch_name: str) -> int:
    from repro.configs.registry import archs

    arch = archs()[arch_name]
    if arch.family == "lm":
        per = len(arch.config.layer_pattern)
        return max(arch.config.n_layers // per, 1)
    if arch.family == "gnn":
        return arch.config.n_layers
    return 1


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    from repro.configs.registry import build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = build_cell(arch_name, shape_name, mesh)
    rec = {"arch": arch_name, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": mesh.devices.size, "ok": False}
    try:
        from jax.sharding import NamedSharding, PartitionSpec

        def to_sharding(tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), tree,
                is_leaf=lambda x: isinstance(x, PartitionSpec),
            )

        # set_mesh installs the abstract mesh that in-model
        # shard_map/constraints see under jit.
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell.step_fn,
                             in_shardings=to_sharding(cell.in_specs),
                             out_shardings=None if cell.out_specs is None
                             else to_sharding(cell.out_specs),
                             donate_argnums=cell.donate)
            lowered = jitted.lower(*cell.arg_shapes)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        hlo = compiled.as_text()
        mult = loop_multiplier_for(arch_name)
        coll = parse_collectives(hlo, mult)
        rec.update(
            ok=True,
            loop_multiplier=mult,
            # cost_analysis counts while-loop bodies ONCE; the layer stack
            # dominates, so adjusted ~= raw * scan length (validated against
            # analytic 6*N*D in EXPERIMENTS.md §Roofline).
            flops_adjusted=float(cost.get("flops", 0.0)) * mult
            if isinstance(cost, dict) else None,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
                "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
                + (getattr(mem, "argument_size_in_bytes", 0) or 0),
            },
            flops=cost.get("flops", 0.0),
            bytes_accessed=cost.get("bytes accessed", 0.0),
            collectives=coll,
            model_flops=cell.model_flops_per_step,
        )
        if verbose:
            print(f"[OK] {arch_name} x {shape_name} on {rec['mesh']}: "
                  f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
                  f"peak/device {rec['memory']['peak_bytes']/2**30:.2f} GiB "
                  f"HLO GFLOPs {rec['flops']/1e9:.1f} "
                  f"coll {coll['total_bytes']/2**20:.1f} MiB")
            print(f"     memory_analysis: {mem}")
    except Exception as e:  # noqa: BLE001 — record failures, keep sweeping
        rec["error"] = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[FAIL] {arch_name} x {shape_name}: {rec['error']}",
                  file=sys.stderr)
    return rec


def run_sketch_cell(*, multi_pod: bool, mode: str = "a2a",
                    budget_mb: int = 64, batch: int = 1 << 20,
                    verbose: bool = True) -> dict:
    """Dry-run the PAPER'S system at pod scale: partition-parallel kMatrix
    ingest (partitions sharded over 'model' like experts, edges over the
    DP axes, all_to_all or all_gather dispatch) + merged query."""
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import KMatrix, vertex_stats_from_sample
    from repro.distributed.sketch_parallel import make_pp_ingest
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": f"kmatrix-stream-{mode}", "shape": f"ingest_{batch}",
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": mesh.devices.size, "ok": False}
    t0 = time.time()
    try:
        rng = np.random.default_rng(0)
        src = rng.zipf(1.2, 200_000).astype(np.int32) % (1 << 20)
        dst = rng.integers(0, 1 << 20, 200_000).astype(np.int32)
        stats = vertex_stats_from_sample(src, dst)
        sk = KMatrix.create(bytes_budget=budget_mb << 20, stats=stats,
                            depth=7, seed=0, partitioner="banded",
                            n_bands=64)  # >= model axis for balanced owners
        n_rep = mesh.devices.size
        pool = jax.ShapeDtypeStruct((n_rep * sk.pool.shape[0],
                                     sk.pool.shape[1]), jnp.int32)
        conn = jax.ShapeDtypeStruct((n_rep * sk.conn.shape[0],)
                                    + sk.conn.shape[1:], jnp.int32)
        edges = jax.ShapeDtypeStruct((batch,), jnp.int32)
        with jax.set_mesh(mesh):
            fn, owner = make_pp_ingest(sk, mesh, mode=mode)
            lowered = jax.jit(fn, donate_argnums=(0, 1)).lower(
                pool, conn, edges, edges, edges)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        coll = parse_collectives(compiled.as_text(), 1)
        rec.update(ok=True, compile_s=round(time.time() - t0, 2),
                   memory={"peak_bytes": (mem.temp_size_in_bytes or 0)
                           + (mem.argument_size_in_bytes or 0)},
                   flops=cost.get("flops", 0.0),
                   bytes_accessed=cost.get("bytes accessed", 0.0),
                   collectives=coll, model_flops=0.0, loop_multiplier=1)
        if verbose:
            print(f"[OK] kmatrix-stream[{mode}] on {rec['mesh']}: "
                  f"compile {rec['compile_s']}s peak/device "
                  f"{rec['memory']['peak_bytes']/2**30:.3f} GiB "
                  f"coll {coll['total_bytes']/2**20:.1f} MiB/batch "
                  f"owners balanced over {mesh.shape['model']} shards")
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[FAIL] kmatrix-stream[{mode}]: {rec['error']}",
                  file=sys.stderr)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sketch", action="store_true",
                    help="dry-run the paper's partition-parallel sketch")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    args = ap.parse_args()

    if args.sketch:
        n_fail = 0
        with open(args.out, "a") as f:
            for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
                for mode in ["a2a", "allgather"]:
                    rec = run_sketch_cell(multi_pod=mp, mode=mode)
                    f.write(json.dumps(rec) + "\n")
                    n_fail += 0 if rec["ok"] else 1
        sys.exit(1 if n_fail else 0)

    from repro.configs.registry import all_cells

    if args.all:
        cells = all_cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_fail = 0
    with open(args.out, "a") as f:
        for arch, shape in cells:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp)
                f.write(json.dumps(rec) + "\n")
                f.flush()
                n_fail += 0 if rec["ok"] else 1
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
