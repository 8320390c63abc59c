import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

"""Multi-pod dry-run: .lower().compile() every (arch x shape x mesh) cell.

For each cell this produces, WITHOUT allocating any model-sized array:
  * proof the sharded program compiles on the production mesh
    (16x16 single-pod and 2x16x16 multi-pod);
  * compiled.memory_analysis()  — per-device bytes (fits / doesn't fit);
  * compiled.cost_analysis()    — HLO FLOPs + bytes for SSRoofline;
  * collective traffic parsed from the optimized HLO (runtime/hlo.py).

Results are cached as JSON under experiments/dryrun/ so repeated invocations
only compile missing cells; launch/roofline.py and EXPERIMENTS.md consume
the cache.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--arch-filter moe]
"""

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch.mesh import describe, make_production_mesh
from repro.models import steps as model_steps
from repro.models.config import SHAPES, input_specs
from repro.models.registry import build_model
from repro.models.sharding import make_policy
from repro.optim import adamw
from repro.runtime import hlo

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def _sds(spec, sharding):
    return jax.ShapeDtypeStruct(spec.shape, spec.dtype, sharding=sharding)


def _shard_specs(tree, shardings):
    return jax.tree.map(_sds, tree, shardings)


def _batch_sharding(mesh, policy, spec):
    """Sharding for one input leaf: batch axis over dp (replicated when the
    batch does not divide the dp extent, e.g. long_500k's batch of 1)."""
    nd = len(spec.shape)
    if spec.shape[0] % policy.dp_size:
        return NamedSharding(mesh, P(*([None] * nd)))
    return NamedSharding(mesh, P(policy.dp_axes, *([None] * (nd - 1))))


def build_cell(arch: str, shape: str, multi_pod: bool, cfg_transform=None):
    """Returns (step_fn, args_specs, kwargs_specs, static_info).
    cfg_transform: optional ModelConfig -> ModelConfig hook (the roofline
    analysis variant rewrites scan/unroll/layer-count knobs through it)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    policy = make_policy(cfg, mesh)
    model = build_model(cfg)
    seq, batch, kind = SHAPES[shape]

    param_shapes = model.init_shapes()
    param_sh = policy.params_shardings(cfg, param_shapes)
    params_specs = _shard_specs(param_shapes, param_sh)

    inputs = input_specs(cfg, shape)
    kwargs = {}
    for k, v in inputs.items():
        if k == "cache":
            cache_shapes = model.cache_shapes(batch, seq)
            cache_sh = policy.cache_shardings(cfg, cache_shapes)
            kwargs["cache"] = _shard_specs(cache_shapes, cache_sh)
        elif k == "cache_index":
            kwargs["cache_index"] = _sds(v, NamedSharding(mesh, P()))
        else:
            kwargs[k] = _sds(v, _batch_sharding(mesh, policy, v))

    info = {"arch": arch, "shape": shape, "kind": kind,
            "mesh": describe(mesh), "chips": int(mesh.devices.size),
            "params": model.param_count(),
            "active_params": model.active_param_count(),
            "seq": seq, "batch": batch}

    if kind == "train":
        opt_cfg = adamw.AdamWConfig(moment_dtype=cfg.opt_state_dtype)
        opt_shapes = jax.eval_shape(lambda p: adamw.init(opt_cfg, p),
                                    param_shapes)
        opt_sh = {"m": param_sh, "v": param_sh,
                  "step": NamedSharding(mesh, P())}
        opt_specs = _shard_specs(opt_shapes, opt_sh)
        step = model_steps.make_train_step(cfg, opt_cfg, policy=policy)
        fn = jax.jit(step, donate_argnums=(0, 1),
                     out_shardings=(param_sh, opt_sh, None))
        args = (params_specs, opt_specs)
    elif kind == "prefill":
        step = model_steps.make_prefill_step(cfg, policy=policy,
                                             cache_capacity=seq)
        fn = jax.jit(step)
        args = (params_specs,)
    else:  # decode
        step = model_steps.make_decode_step(cfg, policy=policy)
        fn = jax.jit(step, donate_argnames=("cache",))
        args = (params_specs,)
    return fn, args, kwargs, info


def run_cell(arch: str, shape: str, multi_pod: bool,
             save: bool = True) -> dict:
    label = f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}"
    t0 = time.time()
    fn, args, kwargs, info = build_cell(arch, shape, multi_pod)
    lowered = fn.lower(*args, **kwargs)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rec = dict(info)
    rec["label"] = label
    rec["lower_s"] = round(t_lower, 2)
    rec["compile_s"] = round(t_compile, 2)

    try:
        ca = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and (
                           "flops" in k or "bytes" in k or "utilization" in k)}
    except Exception as e:  # pragma: no cover
        rec["cost"] = {"error": str(e)}
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k)) for k in dir(ma)
            if k.endswith("_size_in_bytes") and not k.startswith("_")}
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}

    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    stats = hlo.collective_stats(text)
    rec["collectives"] = {
        "bytes_by_kind": stats.bytes_by_kind,
        "count_by_kind": stats.count_by_kind,
        "total_bytes": stats.total_bytes,
        "redundant": stats.redundant[:20],
    }
    print(f"[dryrun] {label}: compile={t_compile:.1f}s "
          f"flops={rec['cost'].get('flops', float('nan')):.3e} "
          f"coll={stats.total_bytes/2**30:.3f}GiB "
          f"({stats.total_count} ops)")
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        with open(os.path.join(RESULTS_DIR, label + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--arch-filter", default="")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    jobs = []
    if args.all:
        for arch in list_archs():
            if args.arch_filter and args.arch_filter not in arch:
                continue
            cfg = get_config(arch)
            for shape in cfg.shapes:
                for mp in meshes:
                    jobs.append((arch, shape, mp))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape (or --all) required")
        for mp in meshes:
            jobs.append((args.arch, args.shape, mp))

    os.makedirs(RESULTS_DIR, exist_ok=True)
    failures = []
    for arch, shape, mp in jobs:
        label = f"{arch}__{shape}__" + ("pod2" if mp else "pod1")
        path = os.path.join(RESULTS_DIR, label + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[dryrun] {label}: cached, skipping")
            continue
        try:
            run_cell(arch, shape, mp)
        except Exception as e:
            failures.append((label, repr(e)))
            print(f"[dryrun] {label}: FAILED {e!r}")
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for l, e in failures:
            print(f"  {l}: {e}")
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


if __name__ == "__main__":
    main()
