"""Multi-pod dry-run: lower + compile every (architecture × shape) cell on
the production meshes, print memory/cost analysis, and dump roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out FILE]

This proves the distribution config is coherent: sharding mismatches,
compile-time OOM, or unsupported collectives fail here.
"""
# The dry-run (and ONLY the dry-run) fakes 512 host devices; this must run
# before ANY other import that could initialize jax.
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp                     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs.registry import arch_ids, shapes_for      # noqa: E402
from repro.distributed import policy        # noqa: E402
from repro.distributed.sharding import sharding_ctx          # noqa: E402
from repro.launch.hbm_model import hbm_floor_bytes           # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402
from repro.launch.roofline import (collective_bytes,         # noqa: E402
                                   parse_memory_analysis, roofline_terms)
from repro.models.api import build_bundle   # noqa: E402

__all__ = ["dryrun_cell", "dryrun_engine_cell"]

# the production meshes are v5e pods: their roofline uses v5e peaks, not
# those of the host the dry-run compiles on
TARGET_KIND = "TPU v5 lite"


def _batch_of(specs: dict, shape_id: str) -> int:
    for k in ("tokens", "token", "ids"):
        if k in specs:
            return specs[k].shape[0]
    return 0


def _named(mesh, spec_tree, pspec_tree):
    return jax.tree.map(
        lambda s, p: NamedSharding(mesh, p if p is not None else P()),
        spec_tree, pspec_tree,
        is_leaf=lambda x: x is None or isinstance(x, P))


def _lower_cell(arch: str, shape_id: str, mesh, override=None):
    """Lower + compile one cell; returns (bundle, compiled)."""
    bundle = build_bundle(arch, override=override)
    spec = shapes_for(arch)[shape_id]
    kind = spec["kind"]
    step = bundle.steps[kind]
    in_specs = bundle.input_specs(shape_id)
    batch = _batch_of(in_specs, shape_id)
    rules = policy.activation_rules(bundle.cfg, mesh, kind, batch=batch)

    init = (bundle.init_fn_for(shape_id) if bundle.family == "gnn"
            else bundle.init_fn)
    params_shape = jax.eval_shape(init, jax.random.PRNGKey(0))
    p_pspecs = policy.param_pspecs(params_shape, bundle.cfg, mesh)
    p_shard = _named(mesh, params_shape, p_pspecs)

    dp = policy.dp_axes(mesh)
    if bundle.family == "gnn":
        dp = policy._flat_axes(mesh)   # graphs shard over the whole fleet
    dp_n = policy._size(mesh, dp)

    def leaf_pspec(s):
        # shard the leading dim over DP only where it divides evenly
        if len(s.shape) >= 1 and s.shape[0] % dp_n == 0 and s.shape[0] > 0:
            return P(dp, *([None] * (len(s.shape) - 1)))
        return P()

    b_pspec = jax.tree.map(leaf_pspec, in_specs)
    b_shard = _named(mesh, in_specs, b_pspec)

    with sharding_ctx(mesh, rules):
        if kind == "train" or bundle.family == "gnn":
            opt_shape = jax.eval_shape(bundle.optimizer.init, params_shape)
            o_pspecs = jax.tree.map(lambda s: P(), opt_shape)
            o_pspecs["m"] = p_pspecs
            o_pspecs["v"] = p_pspecs
            o_shard = _named(mesh, opt_shape, o_pspecs)
            fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_shape, opt_shape, in_specs)
        elif kind == "decode":
            cache_shape = bundle.state_specs(shape_id, params_shape)
            c_rule = rules.get("mla_cache" if bundle.cfg.attention == "mla"
                               else "cache_bsnd")
            c_pspec = jax.tree.map(
                lambda s: P(*((None,) + tuple(c_rule)))
                if c_rule is not None else P(), cache_shape)
            c_shard = _named(mesh, cache_shape, c_pspec)
            fn = jax.jit(step, in_shardings=(p_shard, c_shard, b_shard),
                         donate_argnums=(1,))
            lowered = fn.lower(params_shape, cache_shape, in_specs)
        else:   # prefill / serve / retrieval
            fn = jax.jit(step, in_shardings=(p_shard, b_shard))
            lowered = fn.lower(params_shape, in_specs)

    return bundle, lowered.compile(), kind


def _cost_of(compiled):
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def dryrun_cell(arch: str, shape_id: str, mesh, *, verbose: bool = True,
                extrapolate: bool = True, overrides: dict | None = None):
    """Lower + compile one (arch, shape) cell on `mesh`; memory analysis from
    the full-depth compile.

    Scan-trip-count correction: XLA cost_analysis counts a `while` (layer
    scan) body once, so for scan-stacked families (lm/recsys) flops / bytes /
    collective-bytes are extrapolated linearly from 1- and 2-layer compiles:
    cost(L) = c1 + (L-1)·(c2-c1). GNN models unroll layers in Python, so
    their HLO is already full-depth.
    """
    t0 = time.time()
    bundle, compiled, kind = _lower_cell(arch, shape_id, mesh,
                                         override=overrides)
    mem = parse_memory_analysis(compiled.memory_analysis())
    flops, hbm, coll = _cost_of(compiled)

    layer_field = {"lm": "n_layers", "recsys": "n_blocks"}.get(bundle.family)
    n_layers = getattr(bundle.cfg, layer_field) if layer_field else 1
    if extrapolate and layer_field and n_layers >= 2:
        # unrolled 1- and 2-layer compiles (python loop → full-depth HLO per
        # layer) give exact per-layer costs; the scanned full compile above
        # supplies the memory analysis.
        ov = {layer_field: 1, "unroll": True, **(overrides or {})}
        seq = shapes_for(arch)[shape_id].get("seq_len",
                                             getattr(bundle.cfg, "seq_len", 0))
        if bundle.family == "lm":
            # every scan must collapse to trip-count 1 for exact costs:
            # grad-accum scan → 1 microbatch, flash q/k scans → one block,
            # CE chunk scan → one chunk. Totals are invariant to these knobs.
            ov.update(grad_accum=1, q_chunk=seq, k_chunk=seq, loss_chunk=seq)
        elif bundle.family == "recsys":
            ov.update(q_chunk=seq, k_chunk=seq, batch_chunk=1 << 30)
        _, c1, _ = _lower_cell(arch, shape_id, mesh, override=ov)
        _, c2, _ = _lower_cell(arch, shape_id, mesh,
                               override={**ov, layer_field: 2})
        f1, b1, k1 = _cost_of(c1)
        f2, b2, k2 = _cost_of(c2)
        flops = f1 + (n_layers - 1) * (f2 - f1)
        hbm = b1 + (n_layers - 1) * (b2 - b1)
        coll = {k: k1.get(k, 0) + (n_layers - 1) * (k2.get(k, 0) - k1.get(k, 0))
                for k in set(k1) | set(k2)}

    chips = mesh.size
    # memory term: analytic per-device HBM floor (launch/hbm_model.py) — the
    # XLA:CPU byte count is kept as an aux field but is not TPU-meaningful.
    hbm_floor = hbm_floor_bytes(bundle, shape_id, mesh)
    terms = roofline_terms({"flops": flops, "bytes accessed": hbm_floor}, "",
                           chips, TARGET_KIND,
                           model_flops=bundle.model_flops(shape_id))
    terms.coll_breakdown = coll
    terms.coll_bytes = float(sum(coll.values()))
    terms.collective_s = terms.coll_bytes / 50e9
    res = {
        "arch": arch, "shape": shape_id, "mesh": dict(mesh.shape),
        "chips": chips, "kind": kind,
        "memory": mem, "roofline": terms.row(),
        "coll_breakdown": terms.coll_breakdown,
        "coll_bytes_per_dev": terms.coll_bytes,
        "hbm_floor_per_device": hbm_floor,
        "hbm_bytes_hlo_raw": hbm,
        "compile_s": round(time.time() - t0, 1),
        "ok": True,
    }
    res["roofline"]["collective_s"] = terms.collective_s
    res["roofline"]["dominant"] = terms.dominant
    if verbose:
        print(f"[{arch} × {shape_id} × {chips}chips] "
              f"compile {res['compile_s']}s  "
              f"mem/dev={_fmt_b(mem.get('argument_size_in_bytes'))}+"
              f"{_fmt_b(mem.get('temp_size_in_bytes'))}tmp  "
              f"dominant={terms.dominant}  "
              f"t_comp={terms.compute_s:.2e}s t_mem={terms.memory_s:.2e}s "
              f"t_coll={terms.collective_s:.2e}s "
              f"useful={terms.useful_fraction:.2f}", flush=True)
    return res


def _fmt_b(b):
    if b is None:
        return "?"
    for unit in ["B", "KB", "MB", "GB", "TB"]:
        if abs(b) < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}PB"


# ------------------------------------------------------ CEMR engine cell
def dryrun_engine_cell(mesh, *, frontier_rows: int = 65_536,
                       space: int = 262_144, k_bwd: int = 3,
                       verbose: bool = True):
    """Dry-run of the CEMR vectorized extension step on the production mesh:
    frontier rows sharded over (pod×)data, bitmap words over model, adjacency
    tables replicated over data and word-sharded over model. Proves the
    matching engine's distribution config compiles (queries scale over pods
    via the work-queue runtime)."""
    words = space // 32
    t_specs = tuple(jax.ShapeDtypeStruct((space, words), jnp.uint32)
                    for _ in range(k_bwd))
    idx_spec = jax.ShapeDtypeStruct((frontier_rows, k_bwd), jnp.int32)
    dp = policy.dp_axes(mesh)

    def extend(idxs, *tables):
        r = None
        for j, tbl in enumerate(tables):
            rows = tbl[idxs[:, j]]
            r = rows if r is None else (r & rows)
        pop = jax.lax.population_count(r).astype(jnp.int32).sum(-1)
        return r, pop

    t_shard = tuple(NamedSharding(mesh, P(None, "model")) for _ in range(k_bwd))
    i_shard = NamedSharding(mesh, P(dp, None))
    fn = jax.jit(extend, in_shardings=(i_shard,) + t_shard)
    lowered = fn.lower(idx_spec, *t_specs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    terms = roofline_terms(cost, compiled.as_text(), mesh.size, TARGET_KIND,
                           model_flops=float(frontier_rows * k_bwd * words))
    res = {"arch": "cemr-engine", "shape": f"T{frontier_rows}_S{space}",
           "mesh": dict(mesh.shape), "chips": mesh.size, "kind": "match",
           "memory": parse_memory_analysis(compiled.memory_analysis()),
           "roofline": terms.row(), "coll_breakdown": terms.coll_breakdown,
           "ok": True}
    if verbose:
        print(f"[cemr-engine × {mesh.size}chips] dominant={terms.dominant} "
              f"t_mem={terms.memory_s:.2e}s t_coll={terms.collective_s:.2e}s")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--engine", action="store_true",
                    help="dry-run the CEMR engine cell")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=int (e.g. --set cp_degree=16)")
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = int(v)

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    results = []
    for mesh in meshes:
        if args.engine:
            results.append(dryrun_engine_cell(mesh))
            continue
        if args.all:
            cells = [(a, s) for a in arch_ids() for s in shapes_for(a)]
        else:
            assert args.arch and args.shape, "--arch and --shape (or --all)"
            cells = [(args.arch, args.shape)]
        for arch, shape_id in cells:
            try:
                results.append(dryrun_cell(arch, shape_id, mesh,
                                           overrides=overrides or None))
            except Exception as e:   # noqa: BLE001 — report, don't die
                traceback.print_exc()
                results.append({"arch": arch, "shape": shape_id,
                                "mesh": dict(mesh.shape), "ok": False,
                                "error": f"{type(e).__name__}: {e}"})
        if not args.engine and args.all:
            results.append(dryrun_engine_cell(mesh))

    n_ok = sum(1 for r in results if r.get("ok"))
    print(f"\n== dry-run: {n_ok}/{len(results)} cells compiled ==")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=str)
        print(f"wrote {args.out}")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
