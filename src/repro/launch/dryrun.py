"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 placeholder
CPU devices host the production meshes; every cell must lower AND compile,
and the compiled artifact yields memory_analysis / cost_analysis / the
optimized HLO from which EXPERIMENTS.md's roofline table is derived.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only-train4k]
"""
# The VERY FIRST lines, before any other import (jax locks device count on init):
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from functools import partial  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.common import SHAPES  # noqa: E402
from repro.configs.registry import ARCHS  # noqa: E402
from repro.launch import costmodel as CM  # noqa: E402
from repro.launch import mesh as M  # noqa: E402
from repro.launch import roofline as R  # noqa: E402
from repro.nn import transformer as T  # noqa: E402
from repro.nn.common import (DEFAULT_RULES, SEQ_PARALLEL_RULES, param_sharding,  # noqa: E402
                             sharding_ctx, spec_for)
from repro.train import optimizer as optim  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def _sds(shape, dtype, mesh, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _sanitize(spec: P, shape, mesh) -> P:
    """Drop mesh axes that don't divide the dim (input shardings must tile
    evenly, unlike activation constraints which GSPMD pads)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    used: set = set()
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = tuple(a for a in (entry if isinstance(entry, tuple) else (entry,))
                     if a not in used)  # a mesh axis may appear only once
        total = int(np.prod([sizes[a] for a in axes])) if axes else 0
        if not axes or dim % total != 0:
            axes = tuple(a for a in axes if dim % sizes[a] == 0)[:1]
            if not axes:
                out.append(None)
                continue
        used.update(axes)
        out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def _tree_sds(shapes_tree, logical_tree, mesh, rules):
    from repro.nn.common import spec_for

    sds_leaves, treedef = jax.tree_util.tree_flatten(shapes_tree)
    lg_leaves = jax.tree_util.tree_leaves(
        logical_tree, is_leaf=lambda x: isinstance(x, tuple))
    assert len(sds_leaves) == len(lg_leaves), (len(sds_leaves), len(lg_leaves))
    new = []
    for sd, lg in zip(sds_leaves, lg_leaves):
        raw = spec_for(lg, mesh, rules)  # may be unsanitized (dups / uneven)
        new.append(jax.ShapeDtypeStruct(
            sd.shape, sd.dtype,
            sharding=NamedSharding(mesh, _sanitize(raw, sd.shape, mesh))))
    return jax.tree_util.tree_unflatten(treedef, new)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------

def input_specs(arch_id: str, shape_name: str, mesh, rules) -> dict:
    spec = ARCHS[arch_id]
    cfg = spec.full()
    s = SHAPES[shape_name]
    B, S = s["batch"], s["seq"]
    kind = s["kind"]
    batch_axes = rules["batch"]
    bspec = spec_for(("batch", "seq"), mesh, rules)
    out = {}
    tok_len = 1 if kind == "decode" else S
    out["tokens"] = _sds((B, tok_len), jnp.int32, mesh,
                         P(bspec[0]) if kind == "decode" else bspec)
    if cfg.mrope_sections is not None:
        out["positions"] = _sds((B, 3, tok_len), jnp.int32, mesh, P(bspec[0], None, None))
        if kind != "decode":
            out["vision_embeds"] = _sds((B, cfg.vision_patches, cfg.d_model),
                                        jnp.bfloat16, mesh, P(bspec[0], None, None))
    if cfg.encoder is not None:
        if kind == "decode":  # encoder ran at prefill; its output is an input
            out["enc_out"] = _sds((B, cfg.encoder.n_frames, cfg.encoder.d_model),
                                  jnp.bfloat16, mesh, P(bspec[0], None, None))
        else:
            out["encoder_frames"] = _sds(
                (B, cfg.encoder.n_frames, cfg.encoder.d_model),
                jnp.bfloat16, mesh, P(bspec[0], None, None))
    return out


def cache_specs(cfg, B: int, S: int, mesh, rules):
    """ShapeDtypeStructs for the decode cache with logical shardings."""
    shapes = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    logical = T.cache_logical(cfg)
    return _tree_sds(shapes, logical, mesh, rules)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def make_opt(spec, n_layers_hint: int = 0):
    dt = jnp.bfloat16 if spec.opt_state_dtype == "bf16" else jnp.float32
    if spec.optimizer == "adafactor":
        return optim.adafactor(1e-2)
    return optim.adamw(3e-4, state_dtype=dt)


def opt_state_specs(spec, param_sds, logical, mesh, rules):
    opt = make_opt(spec)
    state_shapes = jax.eval_shape(opt.init, param_sds)
    if spec.optimizer == "adafactor":
        # Mirror adafactor's factored/unfactored decision per param exactly.
        p_leaves, p_def = jax.tree_util.tree_flatten(param_sds)
        lg_leaves = jax.tree_util.tree_leaves(
            logical, is_leaf=lambda x: isinstance(x, tuple))
        slots = []
        for sd, lg in zip(p_leaves, lg_leaves):
            lg = lg if len(lg) == len(sd.shape) else (None,) * len(sd.shape)
            if len(sd.shape) >= 2 and sd.shape[-1] >= 128 and sd.shape[-2] >= 128:
                slots.append({"vr": lg[:-1], "vc": lg[:-2] + (lg[-1],)})
            else:
                slots.append({"v": lg})
        lg_tree = {"slots": jax.tree_util.tree_unflatten(p_def, slots), "step": ()}
        return _tree_sds(state_shapes, lg_tree, mesh, rules), opt
    lg_tree = {"m": logical, "v": logical, "step": ()}
    return _tree_sds(state_shapes, lg_tree, mesh, rules), opt


def lower_cell(arch_id: str, shape_name: str, multi_pod: bool,
               compile_: bool = True, kv_int8: bool = False,
               serve_bf16: bool = False, no_fsdp: bool = False) -> dict:
    spec = ARCHS[arch_id]
    cfg = spec.full()
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if serve_bf16:  # bf16 serving params: halves param-read traffic at decode
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    s = SHAPES[shape_name]
    B, S, kind = s["batch"], s["seq"], s["kind"]
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    rules = dict(DEFAULT_RULES)
    if no_fsdp:  # small models: TP-only weight sharding, no per-layer gathers
        rules["embed"] = None
    if os.environ.get("REPRO_NO_SP"):  # A/B: Megatron-SP residual sharding off
        rules["seq_res"] = None
    if kind == "decode":
        if B >= 16:  # decode_32k: batch over data, KV-cache seq over model
            rules["seq"] = "model"
        else:  # long_500k: batch of 1 — context-parallel over the whole mesh
            rules["batch"] = None
            rules["seq"] = ("data", "model")
            rules["seq_res"] = None
    t0 = time.time()
    # abstract init: param shapes + logical axes with zero allocation
    shapes_tree, logical = T.abstract_init(cfg)
    params_sds = _tree_sds(shapes_tree, logical, mesh, rules)

    with mesh, sharding_ctx(mesh, rules):
        if kind == "train":
            opt_sds, opt = opt_state_specs(spec, params_sds, logical, mesh, rules)
            # microbatch must stay divisible by the DP degree (shard_map axes)
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            b_rule = rules.get("batch") or ()
            dp = int(np.prod([sizes[a] for a in
                              ((b_rule,) if isinstance(b_rule, str) else b_rule)
                              if a in sizes])) or 1
            accum = max(1, min(spec.grad_accum, B // dp))

            def train_step(params, opt_state, batch):
                if accum > 1:  # microbatched gradient accumulation
                    def micro(carry, mb):
                        (loss, metrics), grads = jax.value_and_grad(
                            T.loss_fn, has_aux=True)(params, cfg, mb)
                        acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                                           carry[0], grads)
                        return (acc, carry[1] + loss), None
                    micro_batch = jax.tree.map(
                        lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                        batch)
                    zeros = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    (grads, loss), _ = jax.lax.scan(
                        micro, (zeros, jnp.float32(0.0)), micro_batch)
                    grads = jax.tree.map(lambda g: g / accum, grads)
                    loss = loss / accum
                else:
                    (loss, metrics), grads = jax.value_and_grad(
                        T.loss_fn, has_aux=True)(params, cfg, batch)
                grads, gnorm = optim.clip_by_global_norm(grads, 1.0)
                params, opt_state = opt.update(grads, opt_state, params)
                return params, opt_state, {"loss": loss, "grad_norm": gnorm}

            batch = input_specs(arch_id, shape_name, mesh, rules)
            lowered = jax.jit(train_step, donate_argnums=(0, 1)).lower(
                params_sds, opt_sds, batch)
            tokens = B * S
        elif kind == "prefill":
            def prefill(params, batch):
                logits, aux = T.forward(params, cfg, batch["tokens"],
                                        positions=batch.get("positions"),
                                        vision_embeds=batch.get("vision_embeds"),
                                        encoder_frames=batch.get("encoder_frames"))
                return logits
            batch = input_specs(arch_id, shape_name, mesh, rules)
            lowered = jax.jit(prefill).lower(params_sds, batch)
            tokens = B * S
        else:  # decode
            cache_sds = cache_specs(cfg, B, S, mesh, rules)

            def serve_step(params, cache, batch):
                return T.decode_step(params, cfg, cache, batch["tokens"],
                                     positions=batch.get("positions"),
                                     enc_out=batch.get("enc_out"))
            batch = input_specs(arch_id, shape_name, mesh, rules)
            lowered = jax.jit(serve_step, donate_argnums=(1,)).lower(
                params_sds, cache_sds, batch)
            tokens = B  # one new token per row
        lower_s = time.time() - t0
        result = {"arch": arch_id, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "kind": kind, "lower_s": round(lower_s, 1)}
        if not compile_:
            result["hlo_collectives"] = R.collective_bytes(lowered.as_text())
            return result
        t1 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        }
        ca = compiled.cost_analysis()
        coll = R.collective_bytes(compiled.as_text())
        chips = mesh.devices.size
        n_params, n_active = T.count_params_cfg(cfg)
        # Analytic flops/bytes (XLA cost_analysis reports while bodies once —
        # see launch/costmodel.py docstring); collectives from the trip-count-
        # aware HLO parse.
        cost = CM.step_cost(cfg, n_params, kind, B, S,
                            param_bytes=2 if serve_bf16 else 4)
        result["cost"] = {
            "flops_analytic": cost.flops, "hbm_bytes_analytic": cost.hbm_bytes,
            "flops_xla_raw": float(ca.get("flops", 0.0)),
            "bytes_xla_raw": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes": coll["total"],
            "collective_counts": coll["counts"],
        }
        result["terms"] = R.roofline_terms(cost.flops, cost.hbm_bytes,
                                           coll["total"], chips)
        mf = R.model_flops(n_params, n_active, tokens, kind)
        result["model_flops"] = mf
        result["useful_frac"] = (min(1.0, mf["model_flops_active"] / cost.flops)
                                 if cost.flops else 0.0)
        result["n_params"] = n_params
        result["n_active"] = n_active
        return result


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             kv_int8: bool = False, serve_bf16: bool = False,
             no_fsdp: bool = False) -> dict:
    skip = ARCHS[arch_id].shapes()[shape_name]["skip"]
    if skip:
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16", "skipped": skip}
    try:
        return lower_cell(arch_id, shape_name, multi_pod, kv_int8=kv_int8,
                          serve_bf16=serve_bf16, no_fsdp=no_fsdp)
    except Exception as e:  # a failing cell is a bug — surface it loudly
        return {"arch": arch_id, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache variant for decode cells (hillclimb)")
    ap.add_argument("--serve-bf16", action="store_true",
                    help="bf16 serving params (halves param traffic at decode)")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="TP-only weight sharding (drops per-layer FSDP gathers)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    os.makedirs(ARTIFACTS, exist_ok=True)
    cells = []
    if args.all:
        for aid in ARCHS:
            for shp in SHAPES:
                cells.append((aid, shp, False))
                cells.append((aid, shp, True))
    else:
        cells.append((args.arch, args.shape, args.multipod))
    results = []
    for aid, shp, mp in cells:
        r = run_cell(aid, shp, mp, kv_int8=args.kv_int8,
                     serve_bf16=args.serve_bf16, no_fsdp=args.no_fsdp)
        results.append(r)
        tag = "SKIP" if "skipped" in r else ("FAIL" if "error" in r else "OK")
        extra = r.get("error", "") if tag == "FAIL" else \
            (R.summarize(r) if tag == "OK" else r.get("skipped", ""))
        print(f"[{tag}] {aid} {shp} {'2x16x16' if mp else '16x16'} {extra}",
              flush=True)
        if "memory" in r:
            print(f"       mem/dev: args={r['memory']['argument_bytes']/2**30:.2f}GiB "
                  f"temp={r['memory']['temp_bytes']/2**30:.2f}GiB "
                  f"lower={r['lower_s']}s compile={r['compile_s']}s", flush=True)
        out_path = args.out or os.path.join(ARTIFACTS, "results.json")
        with open(out_path, "w") as f:
            json.dump(results, f, indent=1, default=str)
    print(f"wrote {len(results)} cells")


if __name__ == "__main__":
    main()
