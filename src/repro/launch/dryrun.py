import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes, record memory/cost/collective analysis for the
roofline (EXPERIMENTS.md §Dry-run / §Roofline).

MUST be invoked as its own process (the XLA_FLAGS line above executes before
any jax import — 512 placeholder host devices).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--mode fsdp|tp]
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import INPUT_SHAPES, get_config, input_specs, list_configs
from repro.configs.base import InputShape, ModelConfig
from repro.launch import hlo_analysis, sharding, steps
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh
from repro.models import decoder

RESULTS_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def skip_reason(cfg: ModelConfig, shape: InputShape) -> str | None:
    if shape.name == "long_500k" and cfg.is_encoder_decoder:
        # enc-dec: no sub-quadratic analogue for a 524k decoder context
        # (see DESIGN.md §5) — the only skipped pair family.
        return "enc-dec: 524k decoder context has no sliding-window analogue"
    return None


def decode_cache_plan(cfg: ModelConfig, shape: InputShape) -> tuple[int, bool]:
    """(cache length, rolling?) for decode shapes."""
    if shape.name == "long_500k":
        if cfg.family in ("ssm", "hybrid"):
            # SSM layers are O(1); jamba's sparse attn layers keep full KV at B=1
            return shape.seq_len, False
        return cfg.long_context_window, True  # dense/MoE: rolling window
    if cfg.sliding_window:
        return min(shape.seq_len, cfg.sliding_window), True
    return shape.seq_len, False


def run_one(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    mode: str = "fsdp",
    remat: bool = True,
    seq_override: int | None = None,
    unroll: bool = False,
    ce_impl: str = "gather",
    embed_mode: str | None = None,
    act_sharding: bool = False,
    ce_chunk: int = 0,
    cross_cache: bool = False,
    ssm_chunk: int = 0,
    cache_batch_only: bool = False,
) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    import dataclasses

    if seq_override:
        shape = dataclasses.replace(shape, seq_len=seq_override)
    if ssm_chunk:
        cfg = dataclasses.replace(cfg, ssm_chunk=ssm_chunk)
    reason = skip_reason(cfg, shape)
    rec: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode,
        "unroll": unroll,
        "ce_impl": ce_impl,
        "embed_mode": embed_mode or "fsdp",
        "act_sharding": act_sharding,
    }
    if reason:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    if act_sharding:
        from jax.sharding import NamedSharding, PartitionSpec as P

        dp = sharding.data_axes(mesh)
        decoder.set_activation_shardings(
            act=NamedSharding(mesh, P(dp, None, None)),
            logits=NamedSharding(mesh, P(dp, None, "model")),
        )
    else:
        decoder.set_activation_shardings()
    key = jax.random.PRNGKey(0)
    max_seq = shape.seq_len + cfg.num_prefix_tokens
    params_shape = jax.eval_shape(lambda: decoder.init_params(cfg, key, max_seq=max_seq))
    p_shard = sharding.params_shardings(params_shape, mesh, mode, embed_mode)
    p_abs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), params_shape, p_shard
    )
    specs = input_specs(cfg, shape)
    in_shard = sharding.input_shardings(specs, mesh)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=in_shard[k]) for k, v in specs.items()
    }

    t0 = time.time()
    if shape.kind == "train":
        step = steps.make_train_step(
            cfg, remat=remat, unroll=unroll, ce_impl=ce_impl, ce_chunk=ce_chunk
        )
        lowered = jax.jit(step, out_shardings=(sharding.replicated(mesh), p_shard)).lower(
            p_abs, batch_abs
        )
    elif shape.kind == "prefill":
        step = steps.make_prefill_step(cfg, unroll=unroll)
        lowered = jax.jit(step).lower(p_abs, batch_abs)
    else:  # decode
        cache_len, rolling = decode_cache_plan(cfg, shape)
        cache_shape = jax.eval_shape(
            lambda: decoder.init_cache(
                cfg, shape.global_batch, cache_len, rolling, cross_cache=cross_cache
            )
        )
        c_shard = sharding.cache_shardings(cache_shape, mesh, cfg, batch_only=cache_batch_only)
        c_abs = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), cache_shape, c_shard
        )
        tok_abs = batch_abs["tokens"]
        pos_abs = batch_abs["positions"]
        if cfg.is_encoder_decoder and cross_cache:
            # beyond-paper: cross K/V cached at prefill; decode needs no encoder input
            step = steps.make_serve_step(cfg, rolling, unroll=unroll)
            lowered = jax.jit(step).lower(p_abs, c_abs, tok_abs, pos_abs)
        elif cfg.is_encoder_decoder:
            step = steps.make_serve_step(cfg, rolling, with_encoder=True, unroll=unroll)
            enc_abs = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_seq, cfg.d_model),
                cfg.dtype,
                sharding=sharding.input_shardings(
                    {"e": jax.ShapeDtypeStruct((shape.global_batch, cfg.encoder_seq, cfg.d_model), cfg.dtype)},
                    mesh,
                )["e"],
            )
            lowered = jax.jit(step).lower(p_abs, c_abs, tok_abs, pos_abs, enc_abs)
        else:
            step = steps.make_serve_step(cfg, rolling, unroll=unroll)
            lowered = jax.jit(step).lower(p_abs, c_abs, tok_abs, pos_abs)
    rec["lower_s"] = round(time.time() - t0, 1)

    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    # --- memory ---
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        rec["memory"] = {"error": str(e)}

    # --- cost ---
    try:
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        hbm = float(ca.get("bytes accessed", 0.0))
        rec["cost"] = {"flops": flops, "bytes_accessed": hbm}
    except Exception as e:
        rec["cost"] = {"error": str(e)}
        flops, hbm = 0.0, 0.0

    # --- collectives + roofline ---
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    rec["collectives"] = {k: v for k, v in coll.items() if k != "counts"}
    rec["collective_counts"] = coll["counts"]
    terms = hlo_analysis.roofline_terms(
        flops, hbm, coll["total"], PEAK_FLOPS_BF16, HBM_BW, ICI_BW
    )
    rec["roofline"] = terms
    # model flops: 6*N_active*D for train, 2*N_active*D for inference fwd
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6 if shape.kind == "train" else 2
    model_flops_total = factor * n_active * tokens
    rec["model_flops_per_chip"] = model_flops_total / n_chips
    rec["useful_flop_ratio"] = (model_flops_total / n_chips) / flops if flops else None
    rec["n_chips"] = n_chips
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="fsdp", choices=["fsdp", "tp"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll the layer loop for analysis-grade cost/collective counting")
    ap.add_argument("--ce", default="gather", choices=["gather", "onehot"])
    ap.add_argument("--embed-mode", default=None, choices=[None, "fsdp", "vocab_only"])
    ap.add_argument("--act-sharding", action="store_true",
                    help="pin activations to batch-sharded layout (§Perf it.3)")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="chunked LM-head+CE over the sequence (§Perf it.6)")
    ap.add_argument("--cross-cache", action="store_true",
                    help="enc-dec decode with cached cross K/V (§Perf it.7)")
    ap.add_argument("--ssm-chunk", type=int, default=0, help="override SSD chunk length (§Perf it.9)")
    ap.add_argument("--cache-batch-only", action="store_true",
                    help="KV cache sharded on batch only (§Perf it.8)")
    ap.add_argument("--seq", type=int, default=None, help="override seq_len (debug)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    pairs = []
    if args.all:
        for a in list_configs():
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        pairs = [(args.arch, args.shape)]

    results = []
    for arch, shape in pairs:
        tag = f"{arch}|{shape}|{'2x16x16' if args.multi_pod else '16x16'}|{args.mode}"
        try:
            rec = run_one(arch, shape, args.multi_pod, args.mode, not args.no_remat,
                          args.seq, args.unroll, args.ce, args.embed_mode, args.act_sharding,
                          args.ce_chunk, args.cross_cache, args.ssm_chunk, args.cache_batch_only)
            status = "SKIP" if "skipped" in rec else "OK"
            print(f"[{status}] {tag} "
                  + (rec.get("skipped", "")
                     or f"compile={rec['compile_s']}s flops={rec['cost'].get('flops', 0):.3g} "
                       f"coll={rec['collectives']['total']:.3g}B bottleneck={rec['roofline']['bottleneck']}"))
        except Exception as e:
            rec = {"arch": arch, "shape": shape, "mode": args.mode,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {tag} {type(e).__name__}: {e}")
        results.append(rec)
        suffix = ""
        if args.unroll:
            suffix += "__unroll"
        if args.ce != "gather":
            suffix += f"__ce-{args.ce}"
        if args.embed_mode and args.embed_mode != "fsdp":
            suffix += f"__emb-{args.embed_mode}"
        if args.act_sharding:
            suffix += "__act"
        if args.ce_chunk:
            suffix += f"__cechunk{args.ce_chunk}"
        if args.cross_cache:
            suffix += "__xcache"
        out = args.out or RESULTS_DIR / (
            f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}__{args.mode}{suffix}.json"
        )
        Path(out).write_text(json.dumps(rec, indent=2, default=str))

    n_ok = sum(1 for r in results if "error" not in r and "skipped" not in r)
    n_skip = sum(1 for r in results if "skipped" in r)
    n_fail = sum(1 for r in results if "error" in r)
    print(f"\n== dry-run summary: {n_ok} ok / {n_skip} skipped / {n_fail} failed ==")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
