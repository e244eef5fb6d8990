import os
# CPU only, for itself and every child of the --all sweep: this process
# imports JAX before it starts them, so on a TPU host it would hold the chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline terms.

MUST be run as its own process (the device-count flag above is set before
any jax import and locks on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape decode_32k --mesh single [--mode sac] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # sweep (subprocesses)

Per cell this prints ``compiled.memory_analysis()`` (proves the program
fits per-chip HBM) and ``compiled.cost_analysis()``, and writes a JSON
record with trip-count-corrected HLO metrics (distributed/hlo_analysis)
and the three roofline terms:

    compute_s    = HLO_dot_FLOPs / 197e12        (per chip, bf16 peak)
    memory_s     = HLO_bytes / 819e9             (per chip HBM)
    collective_s = collective_bytes / 50e9       (per chip ICI link)
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

PEAK_FLOPS = 197e12     # TPU v5e bf16
HBM_BW = 819e9
ICI_BW = 50e9


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------


def np_prod_axes(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    p = 1
    for a in axes:
        p *= sizes.get(a, 1)
    return p


def batch_axes_for(mesh, batch: int):
    """Longest prefix of (pod, data) whose product divides batch."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in sizes and batch % (prod * sizes[a]) == 0:
            axes.append(a)
            prod *= sizes[a]
    return tuple(axes)


def _rec_pspec(shape, batch: int, model_size: int):
    """Heuristic spec for recurrent-state leaves: shard the batch axis,
    plus the first later axis divisible by the model-axis size."""
    spec = [None] * len(shape)
    b_ax = next((i for i, d in enumerate(shape) if d == batch), None)
    if b_ax is not None:
        spec[b_ax] = "__B__"
        for j in range(b_ax + 1, len(shape)):
            if shape[j] % model_size == 0 and shape[j] >= model_size:
                spec[j] = "model"
                break
    return spec


def serve_state_shardings(state_shapes, mesh, batch: int):
    baxes = batch_axes_for(mesh, batch)
    b_entry = baxes if baxes else None
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    def one(path_key, leaf):
        shape = leaf.shape
        if path_key in ("kv_pool", "idx_pool"):
            return NamedSharding(mesh, P(None, b_entry, "model", None))
        if path_key == "self_kv":
            return NamedSharding(mesh, P(None, b_entry, None, None))
        if path_key in ("cache_len", "dec_len"):
            return NamedSharding(mesh, P(b_entry))
        spec = _rec_pspec(shape, batch, model_size)
        spec = [b_entry if s == "__B__" else s for s in spec]
        return NamedSharding(mesh, P(*spec))

    out = {}
    for key, sub in state_shapes.items():
        if key in ("kv_pool", "idx_pool", "self_kv", "cache_len", "dec_len"):
            out[key] = one(key, sub)
        else:  # rec_* pytrees
            out[key] = jax.tree.map(lambda l: one("rec", l), sub)
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------


def parse_opts(env: Optional[str] = None) -> Dict:
    """REPRO_OPTS="hier_topk=1,pool_closure=1,moe_groups=32" -> dict."""
    s = env if env is not None else os.environ.get("REPRO_OPTS", "")
    out: Dict = {}
    for kv in s.split(","):
        if "=" not in kv:
            continue
        k, v = kv.split("=", 1)
        out[k.strip()] = int(v) if v.strip().isdigit() else v.strip()
    return out


def build_cell(arch: str, shape_name: str, mesh, mode: str = "sac",
               grad_accum: int = 8, opts: Optional[Dict] = None):
    """Returns (step_fn, in_shardings, in_specs, meta) for one cell."""
    from repro.configs import get_config, SHAPES_BY_NAME
    from repro.core.pool import make_pooled_fetch, local_fetch
    from repro.core.topk import make_hierarchical_topk
    from repro.distributed import sharding as shd
    from repro.models.model import (build_model, cell_is_supported,
                                    input_specs)
    from repro.training.optimizer import OptConfig, init_opt_state
    from repro.training.train_loop import make_train_step

    import dataclasses as _dc
    opts = dict(parse_opts(), **(opts or {}))
    grad_accum = int(opts.get("grad_accum", grad_accum))
    cfg = get_config(arch)
    if opts.get("kv_quant"):
        cfg = _dc.replace(cfg, sac=_dc.replace(cfg.sac,
                                               kv_quant=opts["kv_quant"]))
    shape = SHAPES_BY_NAME[shape_name]
    skip = cell_is_supported(cfg, shape, mode)
    if skip:
        return None, None, None, {"skip": skip}

    baxes = batch_axes_for(mesh, shape.global_batch)
    rules = shd.TRAIN_RULES if shape.kind == "train" else shd.SERVE_RULES
    if shape.kind != "train" and not baxes:
        # batch unshardable (e.g. long_500k B=1): the data axis is idle, so
        # row-sharding weights over it is free capacity/bandwidth — keep it
        # (the D-unsharded serve rule only pays off when batch owns `data`)
        rules = dict(rules, D=("data",))

    if shape.kind == "decode" and cfg.has_attention:
        fetch = make_pooled_fetch(mesh, batch_axes=baxes)
    else:
        fetch = local_fetch
    topk_fn = None
    if opts.get("hier_topk") and shape.kind == "decode" and cfg.sac.enabled:
        topk_fn = make_hierarchical_topk(mesh, cfg.sac.topk,
                                         batch_axes=baxes)
    if opts.get("moe_groups") == "auto":
        opts["moe_groups"] = int(np_prod_axes(mesh, baxes))
    model = build_model(cfg, fetch_fn=fetch, mode=mode, topk_fn=topk_fn,
                        opts=opts)

    meta = {"arch": arch, "shape": shape_name, "mode": model.mode,
            "kind": shape.kind, "opts": {k: v for k, v in opts.items()},
            "batch": shape.global_batch, "seq": shape.seq_len}

    with shd.use_rules(rules, mesh):
        p_shard = shd.params_shardings(model.specs, mesh, rules=rules)
        b_entry = baxes if baxes else None

        if shape.kind == "train":
            if cfg.enc_dec:
                ga = min(grad_accum, shape.global_batch)
            else:
                ga = grad_accum if shape.global_batch % grad_accum == 0 else 1
            step = make_train_step(model, OptConfig(), ga)
            opt_shard = {"m": jax.tree.map(lambda s: s, p_shard),
                         "v": jax.tree.map(lambda s: s, p_shard),
                         "step": NamedSharding(mesh, P())}
            batch_specs = input_specs(cfg, shape)
            bshard = {k: NamedSharding(
                mesh, P(b_entry, "model" if v.ndim == 3 else None)
                if v.ndim <= 2 else P(b_entry, "model", None))
                for k, v in batch_specs.items()}
            in_sh = (p_shard, opt_shard, bshard)
            p_spec = model.param_shapes()
            opt_spec = jax.eval_shape(init_opt_state, p_spec)
            in_spec = (p_spec, opt_spec, batch_specs)
            meta["grad_accum"] = ga
            return step, in_sh, in_spec, meta

        if shape.kind == "prefill":
            def step(params, batch):
                if cfg.enc_dec:
                    return model.prefill(params, batch["frames"])
                return model.prefill(params, batch["tokens"])
            batch_specs = input_specs(cfg, shape)
            bshard = {k: NamedSharding(
                mesh, P(b_entry, "model", None) if v.ndim == 3
                else P(b_entry, None))
                for k, v in batch_specs.items()}
            return step, (p_shard, bshard), \
                (model.param_shapes(), batch_specs), meta

        # decode
        def step(params, state, tokens):
            return model.decode(params, state, tokens)
        specs = input_specs(cfg, shape, model=model)
        st_shard = serve_state_shardings(specs["state"], mesh,
                                         shape.global_batch)
        tok_shard = NamedSharding(mesh, P(b_entry))
        return step, (p_shard, st_shard, tok_shard), \
            (model.param_shapes(), specs["state"], specs["tokens"]), meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, mode: str,
             out_dir: Optional[str] = None, verbose: bool = True) -> Dict:
    from repro.launch.mesh import make_production_mesh
    from repro.distributed.hlo_analysis import hlo_metrics
    from repro.distributed import sharding as shd
    from repro.configs import get_config, SHAPES_BY_NAME

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    step, in_sh, in_spec, meta = build_cell(arch, shape_name, mesh, mode)
    meta["mesh"] = "multi" if multi_pod else "single"
    meta["n_devices"] = mesh.devices.size
    if step is None:
        meta["status"] = "skipped"
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape_name}: {meta['skip']}")
        return meta

    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    rules = shd.TRAIN_RULES if shape.kind == "train" else shd.SERVE_RULES
    with shd.use_rules(rules, mesh):
        with mesh:
            donate = (1,) if meta["kind"] == "decode" else ()
            lowered = jax.jit(step, in_shardings=in_sh,
                              donate_argnums=donate).lower(*in_spec)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = hlo_metrics(compiled.as_text())

    chips = mesh.devices.size
    compute_s = hlo["flops"] / PEAK_FLOPS
    memory_s = hlo["bytes"] / HBM_BW
    collective_s = hlo["collective_bytes"] / ICI_BW
    model_flops = _model_flops(cfg, shape)
    per_chip_model = model_flops / chips

    rec = dict(meta)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
        mem_per_device={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None) or
            getattr(mem, "temp_size_in_bytes", 0),
        },
        xla_cost={k: cost.get(k) for k in ("flops", "bytes accessed")},
        hlo_flops=hlo["flops"], hlo_bytes=hlo["bytes"],
        collective_bytes=hlo["collective_bytes"],
        collective_breakdown=hlo["collective_breakdown"],
        collective_counts=hlo["collective_counts"],
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=max(("compute", compute_s), ("memory", memory_s),
                     ("collective", collective_s), key=lambda kv: kv[1])[0],
        model_flops=model_flops,
        useful_flops_ratio=(per_chip_model / hlo["flops"]
                            if hlo["flops"] else None),
    )
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} [{rec['mesh']}][{rec['mode']}]"
              f" OK lower={t_lower:.0f}s compile={t_compile:.0f}s")
        print(f"  memory_analysis: args={rec['mem_per_device']['argument_bytes']}"
              f" temp={rec['mem_per_device']['temp_bytes']}")
        print(f"  cost_analysis: flops={cost.get('flops')}"
              f" bytes={cost.get('bytes accessed')}")
        print(f"  roofline: compute={compute_s*1e3:.2f}ms"
              f" memory={memory_s*1e3:.2f}ms"
              f" collective={collective_s*1e3:.2f}ms"
              f" dominant={rec['dominant']}"
              f" useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = os.environ.get("REPRO_TAG", "")
        tag = f"__{tag}" if tag else ""
        name = f"{arch}__{shape_name}__{rec['mesh']}__{rec['mode']}{tag}.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS convention: 6*N*D train (N_active for MoE), 2*N_active
    per generated token for decode, 2*N_active*tokens prefill (+ dense-
    attention quadratic term for attention archs on train/prefill)."""
    n_act = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        base = 6 * n_act * B * S
        if cfg.has_attention:
            base += 6 * cfg.n_attn_layers * B * S * S * cfg.hd \
                * cfg.n_heads * 0.5
        return base
    if shape.kind == "prefill":
        base = 2 * n_act * B * S
        if cfg.has_attention:
            base += 2 * cfg.n_attn_layers * B * S * S * cfg.hd \
                * cfg.n_heads * 2 * 0.5
        return base
    # decode: one token per request
    base = 2 * n_act * B
    if cfg.has_attention and cfg.sac.enabled:
        k = cfg.sac.topk
        dims = (cfg.kv_lora_rank + cfg.qk_rope_dim) if cfg.mla \
            else 2 * cfg.n_kv_heads * cfg.hd
        base += 2 * cfg.n_attn_layers * B * (k * dims + S * cfg.sac.d_idx)
    return base


# ---------------------------------------------------------------------------
# sweep driver
# ---------------------------------------------------------------------------

CELLS_ENV = "REPRO_DRYRUN_CELLS"


def sweep(args):
    """Run every cell in its own subprocess (fresh device-count flag,
    crash isolation); aggregate JSONs land in --out."""
    from repro.configs import ASSIGNED, SHAPES

    archs = args.archs.split(",") if args.archs else ASSIGNED
    shapes = args.shapes.split(",") if args.shapes else [s.name for s in SHAPES]
    meshes = args.meshes.split(",") if args.meshes else ["single", "multi"]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                out = os.path.join(args.out)
                marker = os.path.join(
                    out, f"{arch}__{shape}__{mesh_kind}__{args.mode}.json")
                if args.resume and os.path.exists(marker):
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", mesh_kind, "--mode", args.mode,
                       "--out", out]
                print(">>", " ".join(cmd), flush=True)
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures.append((arch, shape, mesh_kind))
                    print(f"!! FAILED {arch} {shape} {mesh_kind}", flush=True)
    print(f"sweep done; {len(failures)} failures: {failures}")
    return failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--mode", choices=["sac", "dense"], default="sac")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--archs", help="comma list for --all")
    ap.add_argument("--shapes", help="comma list for --all")
    ap.add_argument("--meshes", help="comma list for --all")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args()
    if args.all:
        failures = sweep(args)
        sys.exit(1 if failures else 0)
    rec = run_cell(args.arch, args.shape, multi_pod=args.mesh == "multi",
                   mode=args.mode, out_dir=args.out)
    sys.exit(0 if rec.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()
