import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod AOT dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
initialisation, and the production meshes need 512 placeholder CPU
devices.  Nothing here allocates device memory for the full configs — all
inputs are ShapeDtypeStructs and the compile is ahead-of-time.

Per cell this driver records (experiments/dryrun/<arch>__<shape>__<mesh>.json):
  * memory_analysis  — per-device argument/output/temp bytes (fit proof);
  * cost_analysis    — per-device FLOPs / bytes accessed;
  * collective wire bytes parsed from the optimized HLO (scan-body trip
    counts composed multiplicatively);
  * roofline terms from 1-group/2-group unrolled extrapolation (§Roofline).

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.roofline import V5E, parse_collective_bytes, roofline_from_costs
from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import DECODE_RULES, TRAIN_RULES, build_model, input_specs, sharding_ctx
from repro.models.params import logical_spec
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.serve.steps import make_decode_step, make_prefill_step
from repro.train.steps import make_train_step
from repro.launch.mesh import make_production_mesh

# gradient-accumulation microbatch override per arch for the train_4k cell
# (auto-sized otherwise — the activation-memory knob, EXPERIMENTS.md notes).
MICROBATCH: dict = {}

# target activation volume per microbatch per device (token·dims); sized so
# a layer's transient working set stays well under the 16 GB/chip budget.
_MICRO_TARGET = 16384 * 4096


def default_microbatch(cfg: ModelConfig, shape: ShapeConfig, mesh) -> int:
    if shape.kind != "train":
        return 1
    if cfg.name in MICROBATCH:
        return MICROBATCH[cfg.name]
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    tok_per_dev = shape.global_batch * shape.seq_len / data_shards
    d = max(cfg.d_model, cfg.ssm_expand * cfg.d_model if cfg.ssm_state else 0)
    if cfg.moe_experts:
        # MoE dispatch expands every token into top_k slots — the dominant
        # transient is the [B, S·K, D] permuted activation, not [B, S, D]
        d = max(d, cfg.d_model * max(cfg.moe_top_k // 2, 1))
    layers = cfg.n_layers + cfg.encoder_layers
    # (1) per-microbatch transient working set; (2) remat boundary budget:
    # the layer scan stores one bf16 [tokens, d_model] carry per layer.
    # A microbatch must keep at least one sequence per data shard — smaller
    # slices stop sharding the batch dim and replicate activations.  The
    # two-level remat scan stores ~sqrt(layers) boundaries, reflected here.
    import math

    stored_layers = 2 * math.isqrt(layers) + 2
    n1 = tok_per_dev * d / _MICRO_TARGET
    n2 = tok_per_dev * cfg.d_model * 2 * stored_layers / 4e9
    # (3) f32 logits transient: tokens × padded_vocab/16 × 4 B (the CE
    # masked-sum keeps it sharded over "model", but several copies live
    # through the backward) — dominates for 256k-vocab models
    vocab_shards = mesh.shape.get("model", 1) if cfg.padded_vocab % mesh.shape.get("model", 1) == 0 else 1
    n3 = tok_per_dev * cfg.padded_vocab * 4 / vocab_shards / 2e9
    n = max(1, int(max(n1, n2, n3)))
    n = 1 << (n - 1).bit_length()  # next power of two (divides the batch)
    return min(n, max(1, shape.global_batch // data_shards))


def needs_sp(cfg: ModelConfig, shape: ShapeConfig, mesh) -> bool:
    """Sequence parallelism when the remat boundaries of the largest legal
    microbatch would not fit (the 340B-class cells)."""
    if shape.kind != "train":
        return False
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    tok_micro_dev = shape.seq_len  # one sequence per device, the floor
    boundaries = tok_micro_dev * cfg.d_model * 2 * (cfg.n_layers + cfg.encoder_layers)
    return boundaries > 6e9


def opt_config(cfg: ModelConfig) -> AdamWConfig:
    # int8 Adam moments above 100B params (16 GB/chip budget, DESIGN.md §5)
    state_dtype = "int8" if cfg.param_count() > 1e11 else "float32"
    return AdamWConfig(state_dtype=state_dtype)


def cell_applicable(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    """None if runnable, else the documented skip reason."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return (
            "long_500k needs sub-quadratic decode state; "
            f"{cfg.name} is pure full-attention (skip per assignment sheet)"
        )
    return None


def named(tree, mesh):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, *, n_micro: int | None = None):
    """Returns (step_fn, abstract_args, in_shardings, out_shardings, rules)."""
    model = build_model(cfg)
    batch_specs = input_specs(cfg, shape)
    B = shape.global_batch

    def batch_sharding():
        out = {}
        for k, v in batch_specs.items():
            if k == "tokens":
                logical = ("batch", None)
            else:  # patch_embeds / frames
                logical = ("batch", None, None)
            out[k] = logical_spec(v.shape, logical, rules, mesh)
        return out

    if shape.kind == "train":
        rules = TRAIN_RULES
        ocfg = opt_config(cfg)
        micro = n_micro if n_micro is not None else default_microbatch(cfg, shape, mesh)
        params_abs = model.abstract()
        opt_abs = jax.eval_shape(lambda p: init_opt_state(p, ocfg), params_abs)
        state_abs = {"params": params_abs, "opt": opt_abs}
        pspecs = model.specs(rules, mesh)
        acc_dtype = jnp.bfloat16 if cfg.param_count() > 1e11 else jnp.float32
        step = make_train_step(
            model, ocfg, n_microbatch=micro, remat=True,
            param_shardings=named(pspecs, mesh), acc_dtype=acc_dtype,
        )
        from repro.optim.adamw import opt_state_specs

        state_specs = {"params": pspecs, "opt": opt_state_specs(params_abs, pspecs, ocfg, mesh)}
        args = (state_abs, batch_specs)
        in_sh = (named(state_specs, mesh), named(batch_sharding(), mesh))
        out_sh = (in_sh[0], None)
        extra = {"n_microbatch": micro, "opt_state": ocfg.state_dtype,
                 "acc_dtype": str(jnp.dtype(acc_dtype)), "rules": rules.name,
                 "donate": (0,)}
        return step, args, in_sh, out_sh, rules, extra

    rules = DECODE_RULES
    model_abs = model.abstract()
    pspecs = model.specs(rules, mesh)
    cache_abs = model.abstract_cache(B, shape.seq_len)
    cache_specs = model.cache_specs(rules, mesh, B, shape.seq_len)

    if shape.kind == "prefill":
        step = make_prefill_step(model)
        args = (model_abs, batch_specs, cache_abs)
        in_sh = (named(pspecs, mesh), named(batch_sharding(), mesh), named(cache_specs, mesh))
        out_sh = (in_sh[2], None)
        return step, args, in_sh, out_sh, rules, {"donate": (2,)}

    # decode: one token against a full cache
    step = make_decode_step(model)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = logical_spec((B, 1), ("batch", None), rules, mesh)
    args = (model_abs, cache_abs, tok_abs, pos_abs)
    in_sh = (
        named(pspecs, mesh),
        named(cache_specs, mesh),
        NamedSharding(mesh, tok_spec),
        NamedSharding(mesh, P()),
    )
    out_sh = (in_sh[1], NamedSharding(mesh, tok_spec), None)
    return step, args, in_sh, out_sh, rules, {"donate": (1,)}


def lower_compile(step, args, in_sh, out_sh, mesh, rules, donate=()):
    t0 = time.time()
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=donate)
    with mesh, sharding_ctx(mesh, rules):
        lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def unrolled_cfg(cfg: ModelConfig, k: int) -> ModelConfig:
    """k layer groups, unrolled (for per-layer cost extrapolation)."""
    prologue = cfg.moe_first_dense if cfg.moe_experts else 0
    return dataclasses.replace(
        cfg,
        n_layers=prologue + k * cfg.layer_period,
        encoder_layers=k if cfg.is_encdec else 0,
        scan_layers=False,
    )


def cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    return {k: float(v) for k, v in ca.items() if isinstance(v, (int, float))}


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path, *, roofline: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}

    skip = cell_applicable(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: SKIP ({skip})")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    world = mesh.devices.size
    try:
        step, args, in_sh, out_sh, rules, extra = build_cell(cfg, shape, mesh)
        donate = extra.pop("donate", ())
        lowered, compiled, t_lower, t_compile = lower_compile(
            step, args, in_sh, out_sh, mesh, rules, donate=donate
        )
        ma = compiled.memory_analysis()
        rec.update(extra)
        rec["status"] = "ok"
        rec["lower_s"] = round(t_lower, 2)
        rec["compile_s"] = round(t_compile, 2)
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_estimate_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes
            ),
            "hbm_bytes": int(V5E.hbm_bytes),
        }
        rec["fits_hbm"] = rec["memory"]["peak_estimate_bytes"] <= V5E.hbm_bytes
        rec["cost_analysis"] = cost_dict(compiled)

        hlo = compiled.as_text()
        coll, by_kind = parse_collective_bytes(hlo, world=world)
        rec["collectives"] = {"wire_bytes_per_device": coll, "by_kind": by_kind}

        if roofline:
            prologue = cfg.moe_first_dense if cfg.moe_experts else 0
            n_groups = (cfg.n_layers - prologue) // cfg.layer_period
            costs = []
            for k in (1, 2):
                cfg_k = unrolled_cfg(cfg, k)
                step_k, args_k, in_k, out_k, rules_k, extra_k = build_cell(
                    cfg_k, shape, mesh, n_micro=1
                )
                _, comp_k, _, tc = lower_compile(
                    step_k, args_k, in_k, out_k, mesh, rules_k,
                    donate=extra_k.get("donate", ()),
                )
                costs.append(cost_dict(comp_k))
                rec[f"unrolled_{k}_compile_s"] = round(tc, 2)
            terms = roofline_from_costs(costs[0], costs[1], n_groups, coll)
            rec["roofline"] = terms.as_dict()
            rec["unrolled_costs"] = costs
            n_active = cfg.active_param_count()
            tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
            mult = 6 if shape.kind == "train" else 2
            rec["model_flops_global"] = float(mult * n_active * tokens)
            hlo_global = terms.flops * world
            rec["model_flops_ratio"] = (
                rec["model_flops_global"] / hlo_global if hlo_global else None
            )
        print(
            f"[dryrun] {arch} × {shape_name} × {mesh_name}: OK "
            f"compile={t_compile:.1f}s "
            f"peak={rec['memory']['peak_estimate_bytes']/2**30:.2f}GiB "
            f"fits={rec['fits_hbm']}"
        )
    except Exception as e:  # record the failure; the sweep continues
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: ERROR {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (see configs/)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multipod", "both"])
    ap.add_argument("--all", action="store_true", help="run every (arch × shape)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--skip-done", action="store_true", help="skip cells with an ok JSON")
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multipod"] if args.mesh == "both" else [args.mesh]

    errors = 0
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                out_path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
                if args.skip_done and out_path.exists():
                    try:
                        if json.loads(out_path.read_text()).get("status") in ("ok", "skipped"):
                            continue
                    except Exception:
                        pass
                rec = run_cell(
                    arch, shape, mesh_name, out_dir,
                    roofline=(not args.no_roofline) and mesh_name == "single",
                )
                errors += rec["status"] == "error"
    if errors:
        raise SystemExit(f"[dryrun] {errors} cell(s) recorded status: error")


if __name__ == "__main__":
    main()
