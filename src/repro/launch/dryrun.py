"""Multi-pod dry-run: prove the distribution config is coherent for every
(architecture x input shape x mesh) with abstract lowering + compile only.

  train_4k     -> one FedGiA communication round (the paper's algorithm) —
                  or a baseline's round via --algo
  prefill_32k  -> serve_step prefill (builds the KV cache)
  decode_32k   -> serve_step decode: ONE token against a 32k cache
  long_500k    -> decode with 512k context: recurrent state (ssm/hybrid) or
                  sliding-window ring cache (all attention archs)

For each combination we print/record compiled.memory_analysis() (fits?),
compiled.cost_analysis() (per-chip FLOPs/bytes) and the collective traffic
parsed from the per-device HLO — the inputs to EXPERIMENTS.md §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--algo fedgia|fedavg] [--unrolled]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Run as a script it gives itself 512 fake CPU devices; imported, it leaves
the process's devices alone.
"""
import os

if __name__ == "__main__":
    # before jax is imported: the flag is read once, at backend start-up
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import FedConfig, INPUT_SHAPES, ModelConfig, ShapeConfig
from repro.configs import get_config, list_architectures
from repro.core import make_algorithm
from repro.launch.hlo_analysis import collective_bytes, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models import Transformer
from repro.models.attention import AttnMode


# --------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig, num_clients: int = 0):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    tok = jnp.int32
    if shape.kind == "train":
        m = num_clients
        bc = max(B // m, 1)
        if cfg.input_mode == "tokens":
            return {"tokens": jax.ShapeDtypeStruct((m, bc, S + 1), tok)}
        if cfg.input_mode == "embeds":
            return {
                "embeds": jax.ShapeDtypeStruct((m, bc, S, cfg.d_model), jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((m, bc, S), tok),
            }
        P_img = cfg.embed_prefix_len
        return {
            "embeds": jax.ShapeDtypeStruct((m, bc, P_img, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((m, bc, S - P_img + 1), tok),
        }
    if shape.kind == "prefill":
        if cfg.input_mode == "embeds":
            return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)}
        if cfg.input_mode == "tokens+embeds":
            P_img = cfg.embed_prefix_len
            return {
                "embeds": jax.ShapeDtypeStruct((B, P_img, cfg.d_model), jnp.bfloat16),
                "tokens": jax.ShapeDtypeStruct((B, S - P_img), tok),
            }
        return {"tokens": jax.ShapeDtypeStruct((B, S), tok)}
    # decode: ONE new token; the cache IS the context
    return {"tokens": jax.ShapeDtypeStruct((B, 1), tok)}


def _cache_len(cfg: ModelConfig, shape: ShapeConfig):
    if shape.name == "long_500k":
        return min(cfg.sliding_window, shape.seq_len)
    return shape.seq_len


def _decode_window(cfg: ModelConfig, shape: ShapeConfig):
    return cfg.sliding_window if shape.name == "long_500k" else None


# ------------------------------------------------------------------ builders
def build_train(cfg, shape, fed: FedConfig, mesh, algo_name="fedgia"):
    from repro.sharding import (
        fed_state_specs,
        param_specs,
        sanitize_specs,
        train_batch_specs,
    )

    model = Transformer(cfg)
    fed = dataclasses.replace(fed, algorithm=algo_name)
    algo = make_algorithm(fed, model.loss, model=model)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rng_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state_sds = jax.eval_shape(algo.init, params_sds, rng_sds)
    batch_sds = input_specs(cfg, shape, fed.num_clients)

    state_specs = sanitize_specs(fed_state_specs(fed, cfg, state_sds), state_sds, mesh)
    batch_specs = sanitize_specs(
        train_batch_specs(fed, batch_sds, mesh.axis_names), batch_sds, mesh
    )

    shard = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    metrics_sds = jax.eval_shape(algo.round, state_sds, batch_sds)[1]
    metrics_specs = jax.tree.map(lambda _: P(), metrics_sds)

    fn = jax.jit(
        algo.round,
        in_shardings=(shard(state_specs), shard(batch_specs)),
        out_shardings=(shard(state_specs), shard(metrics_specs)),
    )
    return fn, (state_sds, batch_sds)


def build_prefill(cfg, shape, mesh):
    from repro.sharding import cache_specs, param_specs, sanitize_specs

    model = Transformer(cfg)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    W = _cache_len(cfg, shape)
    B = shape.global_batch

    def prefill_step(params, batch):
        return model.prefill(params, cache_len=W, **batch)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_sds = input_specs(cfg, shape)
    pspecs = sanitize_specs(param_specs(cfg, params_sds), params_sds, mesh)
    bspec = jax.tree.map(
        lambda s: P(
            (tuple(data_axes) if len(data_axes) > 1 else data_axes[0])
            if B > 1 else None,
            *([None] * (len(s.shape) - 1)),
        ),
        batch_sds,
    )
    logits_sds, cache_sds = jax.eval_shape(prefill_step, params_sds, batch_sds)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    cspec = sanitize_specs(
        cache_specs(cfg, cache_sds, B, data_axes, model_size=msize),
        cache_sds, mesh,
    )
    bspec = sanitize_specs(bspec, batch_sds, mesh)
    shard = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    fn = jax.jit(
        prefill_step,
        in_shardings=(shard(pspecs), shard(bspec)),
        out_shardings=(None, shard(cspec)),
    )
    return fn, (params_sds, batch_sds)


def build_decode(cfg, shape, mesh, cache_dtype=jnp.bfloat16):
    from repro.sharding import cache_specs, param_specs, sanitize_specs

    model = Transformer(cfg)
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    W = _cache_len(cfg, shape)
    B = shape.global_batch
    window = _decode_window(cfg, shape)

    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, window=window)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_sds = jax.eval_shape(
        lambda: model.init_cache(B, W, cache_dtype)
    )
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    pspecs = sanitize_specs(param_specs(cfg, params_sds), params_sds, mesh)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    cspec = sanitize_specs(
        cache_specs(cfg, cache_sds, B, data_axes, model_size=msize),
        cache_sds, mesh,
    )
    tspec = P(
        (tuple(data_axes) if len(data_axes) > 1 else data_axes[0]) if B > 1 else None,
        None,
    )
    shard = lambda specs: jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    fn = jax.jit(
        decode_step,
        in_shardings=(
            shard(pspecs),
            shard(cspec),
            NamedSharding(mesh, tspec),
            NamedSharding(mesh, P()),
        ),
        out_shardings=(None, shard(cspec)),
    )
    return fn, (params_sds, cache_sds, tok_sds, pos_sds)


# ----------------------------------------------------- cost extrapolation
# XLA cost_analysis counts lax.scan bodies ONCE (trip counts are not
# multiplied), so the production scan-over-layers lowering under-reports
# FLOPs/bytes/collectives by ~L. The cost pass lowers small UNROLLED
# variants (scan_layers=False: python-loop layers + unrolled attention
# blocks) with 1 and 2 layers per group and extrapolates:
#   total = f(base) + sum_g (L_g - 1) * [f(base + e_g) - f(base)]
# Sequential time recurrences (rwkv6/ssm) cannot be unrolled (T up to 32k);
# their per-step cost is counted once per layer and corrected analytically.
def _group_counts(cfg):
    from repro.models.transformer import _layer_groups

    return {g.name: g.count for g in _layer_groups(cfg)}


def _small_cfg(cfg, counts):
    total = sum(counts.values())
    changes = dict(num_layers=total, scan_layers=False, remat=False)
    if cfg.moe and cfg.first_dense_layers:
        changes["first_dense_layers"] = counts.get("dense", 0)
    return dataclasses.replace(cfg, **changes)


def _lower_costs(cfg_small, shape, fed, mesh, algo_name,
                 cache_dtype=jnp.bfloat16):
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, args = build_train(cfg_small, shape, fed, mesh, algo_name=algo_name)
        elif shape.kind == "prefill":
            fn, args = build_prefill(cfg_small, shape, mesh)
        else:
            fn, args = build_decode(cfg_small, shape, mesh, cache_dtype=cache_dtype)
        compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_total": coll["total"],
        "coll_wire": coll["wire_bytes"],
    }


def _recurrence_correction(cfg, shape, num_clients, num_devices):
    """Per-device analytic correction for sequential time-scans: the HLO
    counts ONE timestep per layer; add the remaining (T-1) steps."""
    if cfg.attention_type not in ("rwkv", "hybrid"):
        return {}
    if shape.kind == "train":
        T = shape.seq_len
        B = shape.global_batch
        bwd_factor = 3.0  # fwd + ~2x bwd
    elif shape.kind == "prefill":
        T, B, bwd_factor = shape.seq_len, shape.global_batch, 1.0
    else:
        return {}  # decode: T=1, nothing missing
    L = cfg.num_layers
    if cfg.attention_type == "rwkv":
        hd = cfg.rwkv_head_size
        step_flops = 10.0 * B * cfg.num_heads * hd * hd
        step_bytes = 4.0 * B * cfg.num_heads * hd * hd * 4  # state r/w fp32
    else:  # hybrid mamba branch
        step_flops = 8.0 * B * cfg.d_model * cfg.ssm_state
        step_bytes = 4.0 * B * cfg.d_model * cfg.ssm_state * 4
    corr = {
        "flops": L * (T - 1) * step_flops * bwd_factor / num_devices,
        "bytes": L * (T - 1) * step_bytes * bwd_factor / num_devices,
        "coll_total": 0.0,
        "coll_wire": 0.0,
    }
    return corr


def extrapolated_costs(cfg, shape, fed, mesh, algo_name, num_clients,
                       cache_dtype=jnp.bfloat16):
    counts_full = _group_counts(cfg)
    base = {name: 1 for name in counts_full}
    f_base = _lower_costs(_small_cfg(cfg, base), shape, fed, mesh, algo_name,
                          cache_dtype=cache_dtype)
    totals = dict(f_base)
    for name, L in counts_full.items():
        if L <= 1:
            continue
        plus = dict(base)
        plus[name] += 1
        f_plus = _lower_costs(_small_cfg(cfg, plus), shape, fed, mesh,
                              algo_name, cache_dtype=cache_dtype)
        for k in totals:
            body = max(f_plus[k] - f_base[k], 0.0)
            totals[k] += (L - 1) * body
    corr = _recurrence_correction(cfg, shape, num_clients, mesh.devices.size)
    for k, v in corr.items():
        totals[k] = totals.get(k, 0.0) + v
    return totals


# ------------------------------------------------------------------- dry run
def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               algo: str = "fedgia", collapsed: bool = True,
               num_clients: int = 0, verbose: bool = True,
               with_costs: bool = True, client_axes=None,
               fsdp: bool = False, replicate_params: bool = False,
               cache_dtype="bfloat16"):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    if client_axes is None:
        client_axes = ("pod", "data") if multi_pod else ("data",)
    if num_clients == 0:
        num_clients = 1
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for a in client_axes:
            num_clients *= sizes[a]
    # FSDP shards client states over the leftover data axes; with
    # replicate_params (no TP) the model axis is free for state sharding
    # too — the elementwise FedGiA update is sharding-agnostic.
    fsdp_axes = tuple(
        a for a in mesh.axis_names
        if a not in client_axes and (a != "model" or replicate_params)
    ) if fsdp else ()
    fed = FedConfig(
        algorithm=algo,
        num_clients=num_clients,
        k0=5,
        alpha=0.5,
        collapsed=collapsed,
        h_policy="scalar",
        client_axes=tuple(client_axes),
        fsdp_axes=fsdp_axes,
        replicate_params=replicate_params,
        state_dtype="bfloat16",
    )

    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            fn, args = build_train(cfg, shape, fed, mesh, algo_name=algo)
        elif shape.kind == "prefill":
            fn, args = build_prefill(cfg, shape, mesh)
        else:
            fn, args = build_decode(cfg, shape, mesh,
                                    cache_dtype=jnp.dtype(cache_dtype))
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    if with_costs:
        # scan-corrected per-device costs via unrolled-small extrapolation
        ext = extrapolated_costs(cfg, shape, fed, mesh, algo, num_clients,
                                 cache_dtype=jnp.dtype(cache_dtype))
        cost = {"flops": ext["flops"], "bytes accessed": ext["bytes"]}
        coll = dict(coll)
        coll["total"] = ext["coll_total"]
        coll["wire_bytes"] = ext["coll_wire"]
    terms = roofline_terms(cost, coll)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "algo": algo if shape.kind == "train" else "serve",
        "collapsed": collapsed,
        "client_axes": list(client_axes),
        "fsdp": fsdp,
        "replicate_params": replicate_params,
        "num_clients": num_clients if shape.kind == "train" else 0,
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "per_device": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "flops": terms["hlo_flops"],
            "hbm_bytes": terms["hlo_bytes"],
        },
        "collectives": {k: v for k, v in coll.items()},
        "roofline": {
            k: terms[k]
            for k in ("t_compute_s", "t_memory_s", "t_collective_s", "bottleneck")
        },
    }
    if verbose:
        fit_gb = (
            ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
        ) / 2**30
        print(
            f"[dryrun] {arch} {shape_name} mesh={rec['mesh']} algo={rec['algo']}"
            f" lower={t_lower:.1f}s compile={t_compile:.1f}s"
        )
        print(
            f"  per-chip: args+out+temp={fit_gb:.2f} GiB"
            f" flops={terms['hlo_flops']:.3e} hbm={terms['hlo_bytes']:.3e}"
            f" coll={coll['total']:.3e}B"
        )
        print(
            f"  roofline: compute={terms['t_compute_s']*1e3:.3f}ms"
            f" memory={terms['t_memory_s']*1e3:.3f}ms"
            f" collective={terms['t_collective_s']*1e3:.3f}ms"
            f" -> {terms['bottleneck']}-bound"
        )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_architectures())
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--algo", default="fedgia")
    ap.add_argument("--unrolled", action="store_true",
                    help="paper-faithful unrolled k0-step ADMM (vs collapsed)")
    ap.add_argument("--num-clients", type=int, default=0)
    ap.add_argument("--no-costs", action="store_true",
                    help="skip the unrolled cost-extrapolation pass")
    ap.add_argument("--client-axes", default="",
                    help="comma-sep mesh axes enumerating clients (e.g. pod)")
    ap.add_argument("--fsdp", action="store_true",
                    help="shard client states over the leftover data axes")
    ap.add_argument("--replicate-params", action="store_true",
                    help="pure DP within clients (no tensor parallelism)")
    ap.add_argument("--cache-dtype", default="bfloat16",
                    help="KV-cache dtype for decode shapes (e.g. float8_e4m3fn)")
    ap.add_argument("--tag", default="", help="suffix for the output file")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    combos = (
        [(a, s) for a in list_architectures() for s in INPUT_SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = []
    for arch, shape in combos:
        tag = f"{arch}_{shape}_{'2pod' if args.multi_pod else '1pod'}_{args.algo}" + (
            "_unrolled" if args.unrolled else ""
        ) + (f"_{args.tag}" if args.tag else "")
        path = os.path.join(args.out, tag + ".json")
        try:
            rec = dryrun_one(
                arch, shape, multi_pod=args.multi_pod, algo=args.algo,
                collapsed=not args.unrolled, num_clients=args.num_clients,
                with_costs=not args.no_costs,
                client_axes=(tuple(args.client_axes.split(","))
                             if args.client_axes else None),
                fsdp=args.fsdp, replicate_params=args.replicate_params,
                cache_dtype=args.cache_dtype,
            )
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        except Exception as e:  # noqa: BLE001 — record and continue
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print(f"\nall {len(combos)} dry-runs compiled OK")


if __name__ == "__main__":
    main()
