import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell against the production mesh,
prove memory fit, and extract roofline terms (deliverable g).

The two lines above MUST stay the first statements in this module —
jax locks the device count on first init.  Do not import this module
from tests (it would poison their single-device view); run it as
``PYTHONPATH=src python -m repro.launch.dryrun [--arch A --shape S ...]``.

Per cell we emit artifacts/dryrun/<mesh>/<arch>__<shape>.json with:
  * compiled memory_analysis (bytes per device) from the **production
    lowering** (scan-over-layers + flash attention) — the fit/sharding
    proof,
  * per-device HLO FLOPs / bytes / collective bytes from a pair of
    **unrolled reduced-depth lowerings** (L=1 unit and L=2 units,
    FLASH_UNROLL): XLA's cost analysis counts while-loop bodies exactly
    once, so scanned programs undercount by ~L x; the L-pair delta gives
    the exact per-layer contribution, extrapolated to full depth,
  * collective bytes = sum of *result* sizes of every all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute in the
    post-SPMD HLO (operand types are not printed in HLO text; result
    size is the received-bytes proxy, all-reduce counted once ~ ring
    reduce-scatter+all-gather),
  * the three roofline terms vs v5e peaks + MODEL_FLOPS usefulness ratio.
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, SHAPES_BY_NAME, get_config, supports_shape
from repro.configs.base import ModelConfig, RunShape
from repro.launch.mesh import make_production_mesh
from repro.models import api as mapi
from repro.models.params import abstract_params, logical_axes
from repro.optim import adamw
from repro.sharding import rules as R

# v5e per-chip peaks (assignment brief)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
    "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_stats(hlo_text: str) -> dict:
    """Sum operand/result bytes of every collective op in post-SPMD HLO."""
    out = {k: {"count": 0, "operand_bytes": 0, "result_bytes": 0}
           for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.search(r"=\s+((?:\([^)]*\)|[\w\[\],{}: ])*?)\s*(" +
                      "|".join(_COLLECTIVES) + r")(?:-start|-done)?\((.*)$", ls)
        if not m:
            continue
        result_part, kind, operand_part = m.groups()
        if f"{kind}-done" in ls:
            continue  # counted at -start
        rb = sum(_shape_bytes(x) for x in _SHAPE_RE.finditer(result_part))
        # operands: cut at '), ' attribute boundary
        op_text = operand_part.split("),")[0]
        ob = sum(_shape_bytes(x) for x in _SHAPE_RE.finditer(op_text))
        out[kind]["count"] += 1
        out[kind]["operand_bytes"] += ob
        out[kind]["result_bytes"] += rb
    out["total_operand_bytes"] = sum(
        v["operand_bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_result_bytes"] = sum(
        v["result_bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


# ------------------------------------------------------------------------
# step builders
# ------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, grad_shardings=None):
    api = mapi.get_model(cfg)

    def train_step(params, opt_state, batch):
        def lf(p):
            return mapi.loss_fn(api, p, batch)
        grads, metrics = jax.grad(lf, has_aux=True)(params)
        if grad_shardings is not None:
            # §Perf B3: pin gradients to the parameter layout so XLA
            # emits reduce-scatters instead of variadic full all-reduces
            grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
        new_params, new_opt, om = adamw.update(
            grads, opt_state, params, lr=3e-4)
        metrics.update(om)
        return new_params, new_opt, metrics

    return train_step


def build_decode_step(cfg: ModelConfig):
    api = mapi.get_model(cfg)

    def serve_step(params, cache, tokens):
        return api.decode_step(params, cache, tokens, cfg)

    return serve_step


def build_prefill(cfg: ModelConfig, max_len: int):
    api = mapi.get_model(cfg)

    def prefill_step(params, batch):
        return api.prefill(params, batch["tokens"], cfg, max_len,
                           prefix_embeds=batch.get("prefix_embeds"),
                           cache_dtype=jnp.bfloat16)

    return prefill_step


# ------------------------------------------------------------------------
# cell runner
# ------------------------------------------------------------------------

def model_flops_estimate(cfg: ModelConfig, shape: RunShape) -> float:
    """MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D for inference."""
    api = mapi.get_model(cfg)
    n = api.param_count()
    n -= cfg.vocab_size * cfg.d_model  # exclude embedding gather
    if cfg.is_moe:
        e, k = cfg.num_experts, cfg.experts_per_token
        # expert weights contribute k/e of their flops
        expert_params = 3 * cfg.d_model * cfg.d_ff * cfg.num_experts * cfg.num_layers
        n = n - expert_params + expert_params * k / e
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def lower_cell(cfg: ModelConfig, shape: RunShape, mesh, quant: int | None = None,
               kv_dtype=jnp.bfloat16):
    """Lower one cell.  ``quant``: serve DNA-TEQ codes at that exponent
    width (weights cross HBM/ICI as uint8; LUT+qmeta replicated) — the
    beyond-paper-optimized serving variant of §Perf."""
    from repro.core import lama_layers as ll

    api = mapi.get_model(cfg)
    pdt = jnp.bfloat16 if shape.is_serving else jnp.float32
    aparams = abstract_params(api.specs, pdt)
    axes = logical_axes(api.specs)
    if quant and shape.is_serving:
        aparams, axes = ll.abstract_quantize(aparams, axes, bits=quant)
    mode = "serve" if shape.is_serving else "train"
    p_shard = R.tree_shardings(aparams, axes, mesh, mode)

    abatch = mapi.input_specs(cfg, shape)
    b_shard = R.tree_shardings(
        abatch, R.batch_logical_axes(abatch), mesh, mode,
        params_rank_gate=False)

    if shape.kind == "train":
        aopt = adamw.abstract_state(aparams)
        o_shard = adamw.AdamWState(
            step=R.tree_shardings(aopt.step, (), mesh, mode),
            mu=R.tree_shardings(aopt.mu, axes, mesh, mode),
            nu=R.tree_shardings(aopt.nu, axes, mesh, mode),
        )
        fn = build_train_step(cfg, grad_shardings=p_shard)
        jfn = jax.jit(
            fn,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        return jfn.lower(aparams, aopt, abatch)

    if shape.kind == "prefill":
        fn = build_prefill(cfg, shape.seq_len)
        jfn = jax.jit(fn, in_shardings=(p_shard, b_shard))
        return jfn.lower(aparams, abatch)

    # decode
    if cfg.family == "encdec":
        acache = api.abstract_cache(cfg, shape.global_batch, shape.seq_len,
                                    enc_len=min(shape.seq_len, 4096),
                                    dtype=kv_dtype)
    else:
        acache = api.abstract_cache(cfg, shape.global_batch, shape.seq_len,
                                    dtype=kv_dtype)
    c_axes = R.cache_logical_axes(acache)
    c_shard = R.tree_shardings(acache, c_axes, mesh, "serve",
                               params_rank_gate=False)
    atoks = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t_shard = R.tree_shardings(
        atoks, ("batch", None), mesh, "serve", params_rank_gate=False)
    fn = build_decode_step(cfg)
    jfn = jax.jit(fn, in_shardings=(p_shard, c_shard, t_shard),
                  out_shardings=(None, c_shard), donate_argnums=(1,))
    return jfn.lower(aparams, acache, atoks)


def cost_pair_cfgs(cfg: ModelConfig):
    """(cfg_1unit, cfg_2units, units_full) for depth extrapolation."""
    if cfg.family == "hybrid":
        period = len(cfg.attention_pattern or ("rec", "rec", "local"))
        return (cfg.replace(num_layers=period, scan_layers=False),
                cfg.replace(num_layers=2 * period, scan_layers=False),
                cfg.num_layers / period)
    if cfg.family == "encdec":
        return (cfg.replace(enc_layers=1, dec_layers=1, num_layers=2,
                            scan_layers=False),
                cfg.replace(enc_layers=2, dec_layers=2, num_layers=4,
                            scan_layers=False),
                float(cfg.enc_layers))
    return (cfg.replace(num_layers=1, scan_layers=False),
            cfg.replace(num_layers=2, scan_layers=False),
            float(cfg.num_layers))


def _compile_metrics(cfg, shape, mesh, quant=None,
                     kv_dtype=jnp.bfloat16) -> dict:
    with jax.set_mesh(mesh):
        lowered = lower_cell(cfg, shape, mesh, quant=quant,
                             kv_dtype=kv_dtype)
    compiled = lowered.compile()
    try:
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0) or 0.0)
        byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        flops = byts = 0.0
    coll = collective_stats(compiled.as_text())
    return {"flops": flops, "bytes": byts,
            "coll_bytes": float(coll["total_result_bytes"]),
            "collectives": coll}


def _tree_bytes(tree) -> int:
    return sum(
        int(x.size) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree))


def analytic_hbm_bytes(cfg: ModelConfig, shape: RunShape, chips: int,
                       quant: int | None = None,
                       param_shard_degree: int | None = None,
                       kv_dtype=jnp.bfloat16) -> dict:
    """Fused-execution HBM traffic estimate (per chip), the principled
    memory-roofline term.  XLA's "bytes accessed" on the CPU backend
    counts every unfused op's operands (observed ~10-30x a fused TPU
    program); this model counts what a fused program must move:

    * params read (+ write, + optimizer state r/w + grads for train),
    * KV/state cache read + write (serving),
    * one activation-tensor read+write per fused block op (~c_act per
      layer) + remat recompute reads,
    * logits / loss traffic.
    """
    from repro.core import lama_layers as ll

    api = mapi.get_model(cfg)
    pdt = jnp.bfloat16 if shape.is_serving else jnp.float32
    ap = abstract_params(api.specs, pdt)
    if quant and shape.is_serving:
        ap, _ = ll.abstract_quantize(ap, logical_axes(api.specs), bits=quant)
    p_bytes = _tree_bytes(ap)
    # per-chip params read once per step: /chips under FSDP; /model-degree
    # when serving TP-only (weights replicated over the data axes)
    p_shard = param_shard_degree or chips
    n_params = api.param_count()
    b, s = shape.global_batch, shape.seq_len
    d, L = cfg.d_model, cfg.num_layers
    act_bytes = 2  # bf16

    if shape.kind == "train":
        weight_traffic = (
            2 * p_bytes          # params read + write
            + 4 * 4 * n_params   # mu/nu read + write (f32)
            + 2 * 4 * n_params   # grads write + read
        )
        c_act = 16 if not cfg.is_moe else 24
        act_traffic = L * b * s * d * act_bytes * c_act * (4 / 3)  # remat
        logits_traffic = 3 * b * s * cfg.vocab_size * 4
        total = weight_traffic + act_traffic + logits_traffic
        return {"total_bytes": total, "per_chip_bytes": total / chips,
                "param_bytes": p_bytes}
    elif shape.kind == "prefill":
        cache = api.abstract_cache(cfg, b, s) if cfg.family != "encdec" else \
            api.abstract_cache(cfg, b, s, enc_len=min(s, 4096))
        c_act = 12 if not cfg.is_moe else 18
        per_chip = (p_bytes / p_shard
                    + (_tree_bytes(cache)
                       + L * b * s * d * act_bytes * c_act
                       + b * cfg.vocab_size * 4) / chips)
        return {"total_bytes": per_chip * chips, "per_chip_bytes": per_chip,
                "param_bytes": p_bytes}
    else:  # decode
        cache = api.abstract_cache(cfg, b, s, dtype=kv_dtype) \
            if cfg.family != "encdec" else \
            api.abstract_cache(cfg, b, s, enc_len=min(s, 4096),
                               dtype=kv_dtype)
        cache_b = _tree_bytes(cache)
        per_chip = (p_bytes / p_shard   # every resident weight read per token
                    + (cache_b          # cache read (+ small write)
                       + b * cfg.vocab_size * 4
                       + L * b * d * act_bytes * 12) / chips)
        return {"total_bytes": per_chip * chips, "per_chip_bytes": per_chip,
                "param_bytes": p_bytes}


def wkv_analytic_flops(cfg: ModelConfig, shape: RunShape, layers: float) -> float:
    """WKV time-scan flops (inner lax.scan over time; uncounted by XLA
    cost analysis at prefill/train).  ~6 flops per (K,V) state element."""
    if cfg.family != "rwkv" or shape.kind == "decode":
        return 0.0
    h = cfg.d_model // cfg.rwkv_head_dim
    per_tok = 6.0 * h * cfg.rwkv_head_dim ** 2 * layers
    tokens = shape.global_batch * shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0   # fwd+bwd
    return per_tok * tokens * mult


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             force: bool = False, quant: int | None = None,
             tag: str | None = None, kv_dtype=jnp.bfloat16,
             moe_impl: str | None = None) -> dict:
    from repro.models import layers as mlayers

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    suffix = (f"__q{quant}" if quant else "") + (f"__{tag}" if tag else "")
    out_path = out_dir / mesh_name / f"{arch}__{shape_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    out_path.parent.mkdir(parents=True, exist_ok=True)

    cfg = get_config(arch)
    if moe_impl:
        cfg = cfg.replace(moe_impl=moe_impl)
    shape = SHAPES_BY_NAME[shape_name]
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "quant": quant, "moe_impl": moe_impl,
        "status": "skip" if not supports_shape(cfg, shape) else "pending",
    }
    if rec["status"] == "skip":
        rec["reason"] = "long_500k needs sub-quadratic attention (DESIGN.md §4)"
        out_path.write_text(json.dumps(rec, indent=1))
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    try:
        # ---- phase 1: production lowering (fit + sharding proof) -------
        with jax.set_mesh(mesh):
            lowered = lower_cell(cfg, shape, mesh, quant=quant,
                                 kv_dtype=kv_dtype)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        try:
            ma = compiled.memory_analysis()
            mem = {
                "argument_size_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_size_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_size_bytes": getattr(ma, "temp_size_in_bytes", None),
                "alias_size_bytes": getattr(ma, "alias_size_in_bytes", None),
            }
            args = mem["argument_size_bytes"] or 0
            alias = mem["alias_size_bytes"] or 0
            temp = mem["temp_size_bytes"] or 0
            out_b = mem["output_size_bytes"] or 0
            mem["peak_per_device_bytes"] = args + temp + (out_b - alias)
            mem["fits_16gb_hbm"] = bool(mem["peak_per_device_bytes"] < 16e9)
        except Exception as e:  # backend-dependent
            mem = {"error": str(e)}
        hlo_bytes = len(compiled.as_text())
        del compiled, lowered

        # ---- phase 2: unrolled L-pair cost extraction -------------------
        mlayers.set_flash_unroll(True)
        try:
            c1, c2, units = cost_pair_cfgs(cfg)
            m1 = _compile_metrics(c1, shape, mesh, quant=quant,
                                  kv_dtype=kv_dtype)
            m2 = _compile_metrics(c2, shape, mesh, quant=quant,
                                  kv_dtype=kv_dtype)
        finally:
            mlayers.set_flash_unroll(False)

        def extrap(key):
            d = m2[key] - m1[key]
            if d < 0:
                # L=1 lowered with a different (worse) resharding
                # strategy than L=2; per-layer average of the 2-unit
                # program is the defensible estimate then.
                return m2[key] * (units / 2.0)
            return m1[key] + d * (units - 1.0)

        flops_dev = extrap("flops")
        bytes_dev = extrap("bytes")
        coll_dev = extrap("coll_bytes")
        wkv_adj = wkv_analytic_flops(cfg, shape, units) / chips
        flops_dev += wkv_adj
        p_shard_degree = None
        if shape.is_serving and not R.SERVE_PARAM_FSDP:
            p_shard_degree = mesh.shape["model"]
        amem = analytic_hbm_bytes(cfg, shape, chips, quant=quant,
                                  param_shard_degree=p_shard_degree,
                                  kv_dtype=kv_dtype)

        mf = model_flops_estimate(cfg, shape)
        terms = {
            "t_compute_s": flops_dev / PEAK_FLOPS,
            "t_memory_s": amem["per_chip_bytes"] / HBM_BW,
            "t_memory_hlo_upper_s": bytes_dev / HBM_BW,
            "t_collective_s": coll_dev / ICI_BW,
            "hlo_flops_per_chip": flops_dev,
            "hlo_bytes_per_chip": bytes_dev,
            "analytic_bytes_per_chip": amem["per_chip_bytes"],
            "param_bytes_total": amem["param_bytes"],
            "coll_bytes_per_chip": coll_dev,
            "model_flops_total": mf,
            "model_flops_per_chip": mf / chips,
            "useful_flops_ratio": (mf / chips) / flops_dev if flops_dev else None,
            "wkv_analytic_flops_per_chip": wkv_adj,
        }
        dom = max(("t_compute_s", "t_memory_s", "t_collective_s"),
                  key=lambda k: terms[k])
        terms["dominant"] = dom
        bound = terms[dom]
        terms["roofline_fraction_of_bound"] = (
            (mf / chips / PEAK_FLOPS) / bound if bound else None)

        rec.update({
            "status": "ok",
            "chips": chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "total_s": round(time.time() - t0, 1),
            "memory_analysis": mem,
            "cost_pair": {"unit1": m1, "unit2": m2, "units_full": units},
            "collectives_unit2": m2["collectives"],
            "roofline": terms,
            "hlo_bytes": hlo_bytes,
        })
    except Exception as e:
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quant", type=int, default=None,
                    help="serve weights as DNA-TEQ codes at this width")
    ap.add_argument("--serve-rules", choices=["v1", "v2"], default="v2",
                    help="v1: head-dim-sharded cache; v2: split-K seq-sharded")
    ap.add_argument("--serve-params", choices=["fsdp", "tp"], default="fsdp",
                    help="serving weight placement: ZeRO-gathered or TP-only")
    ap.add_argument("--kv-dtype", choices=["bf16", "f8"], default="bf16",
                    help="KV-cache dtype (f8 = float8_e4m3fn)")
    ap.add_argument("--train-rules", choices=["tp", "cp"], default="tp",
                    help="training parallelism: FSDP+TP or context-parallel")
    ap.add_argument("--moe", choices=["routed", "dense_mixture", "ep_a2a"],
                    default=None, help="override MoE dispatch implementation")
    ap.add_argument("--tag", default=None,
                    help="artifact filename suffix for perf variants")
    args = ap.parse_args()

    R.set_serve_seq_shard(args.serve_rules == "v2")
    R.set_serve_param_fsdp(args.serve_params == "fsdp")
    if args.train_rules == "cp":
        from repro.models import layers as _ml
        R.set_train_cp(True)
        _ml.set_context_parallel(True)

    archs = [args.arch] if args.arch else list(ARCH_NAMES)
    shapes = [args.shape] if args.shape else list(SHAPES_BY_NAME)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.time()
                kvd = jnp.bfloat16 if args.kv_dtype == "bf16" else \
                    jnp.float8_e4m3fn
                rec = run_cell(arch, shape, mp, out_dir, force=args.force,
                               quant=args.quant, tag=args.tag, kv_dtype=kvd,
                               moe_impl=args.moe)
                status = rec.get("status")
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={r['dominant']}"
                             f" tc={r['t_compute_s']:.3e}"
                             f" tm={r['t_memory_s']:.3e}"
                             f" tx={r['t_collective_s']:.3e}")
                elif status == "error":
                    extra = " " + rec.get("error", "")[:120]
                print(f"[{time.time()-t0:7.1f}s] {arch:24s} {shape:12s} "
                      f"{'multi' if mp else 'single':6s} {status}{extra}",
                      flush=True)


if __name__ == "__main__":
    main()
