"""Lama-quantized layers: drop-in dense/einsum over a *unified*
operand-quantization abstraction — weights AND activations may arrive
as DNA-TEQ code carriers (DESIGN.md §Quantization).

Every matmul in the model zoo funnels through :func:`dense` /
:func:`dense_general`.  A weight leaf is either

* a ``jnp`` array (paper-faithful bf16/f32 baseline), or
* a qtensor dict ``{"codes": uint8, "lut": [256], "qmeta": [4]}``
  produced by :func:`quantize_tree` — codes live in HBM (1 B/param), the
  256-entry decode LUT is the VMEM-resident "open row".

An *activation* operand is either a float array or a
:class:`~repro.core.exponential_quant.QTensor` (the structurally
identical carrier, produced by :func:`encode_act` against calibrated
per-tensor params or emitted straight from a kernel's quantize
epilogue).  When both operands are carriers, dispatch goes to the
dual-LUT kernel (paper Eq.1: both operands as exponent codes) and, with
``out_quant`` set, the result comes back as codes too — consecutive
quantized matmuls are code-in/code-out with no f32 intermediate in HBM.

**Fused is the default execution path** (this is the paper's whole
premise — never materialize the wide operand): any einsum spec the zoo
uses is canonicalized to a 2-D ``[M, K] @ [K, N]`` (codes reshaped /
byte-transposed, never decoded) and dispatched to the fused Pallas
kernel, with batched specs vmapped over the kernel.  A
:class:`FusedPolicy` (context-scoped) replaces the old module-global
kernel switch: it picks fused vs. materialize per call and controls
epilogue fusion and the flash-decode attention kernel.  Specs the
canonicalizer cannot express (repeated labels, diagonal-style
contractions) fall back to materialize+einsum.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import exponential_quant as eq


# ----------------------------------------------------------------------
# Execution policy
# ----------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedPolicy:
    """Per-context policy for quantized matmul execution.

    mode:
      * ``"auto"``  — fused kernel wherever the spec canonicalizes (the
        default; interpret-mode on CPU so behaviour is uniform).
      * ``"fused"`` — synonym of auto kept for explicit opt-in call
        sites (scripts/tests that want to state intent).
      * ``"materialize"`` — legacy decode-to-HBM path everywhere.
    """

    mode: str = "auto"              # auto | fused | materialize
    fuse_epilogues: bool = True     # act/bias/gate epilogues in-kernel
    flash_decode: bool = True       # paged attention kernels (else oracles)
    autotune: bool | None = None    # None = only on real TPU
    act_quant: bool = True          # honor act-quant params when present
                                    # (calibrated metas ride the params
                                    # tree; False A/B-disables encoding
                                    # without re-calibrating)


_POLICY = FusedPolicy()


def get_policy() -> FusedPolicy:
    return _POLICY


def set_policy(p: FusedPolicy) -> None:
    global _POLICY
    _POLICY = p


@contextlib.contextmanager
def policy(**overrides):
    """Scoped policy override: ``with ll.policy(mode="materialize"): ...``"""
    global _POLICY
    prev = _POLICY
    _POLICY = dataclasses.replace(prev, **overrides)
    try:
        yield _POLICY
    finally:
        _POLICY = prev


def use_pallas_kernel(enable: bool = True) -> None:
    """Legacy switch (pre-policy API): kept for callers/scripts."""
    set_policy(dataclasses.replace(
        _POLICY, mode="fused" if enable else "materialize"))


def _fused_enabled() -> bool:
    return _POLICY.mode in ("auto", "fused")


def materialize(w, dtype=jnp.bfloat16) -> jax.Array:
    """Decode a quantized carrier (weight leaf dict or activation
    :class:`~repro.core.exponential_quant.QTensor`) to a dense array of
    ``dtype``.  This is the ONLY place codes become floats outside a
    kernel — the zero-materialization tests guard it."""
    if eq.is_qtensor(w):
        codes, lut, _ = eq.qt_parts(w)
        return lut.astype(dtype)[codes.astype(jnp.int32)]
    return w.astype(dtype)


def encode_act(x: jax.Array, aq: dict) -> eq.QTensor:
    """Encode an activation against calibrated per-tensor params.

    ``aq`` is one act-quant site entry ``{"lut": [256], "qmeta": [4]}``
    (per-layer slices of the calibrated tree that rides inside
    ``params["blocks"]["act_q"]``).  The result is a :class:`QTensor`
    carrier that every dense/einsum dispatch site accepts in place of a
    float array — downstream matmuls read uint8 codes from HBM and
    decode in-kernel."""
    return eq.QTensor(eq.encode_meta(x, aq["qmeta"]), aq["lut"],
                      aq["qmeta"])


def maybe_encode_act(x, act_q, site: str):
    """Encode ``x`` when act-quant params for ``site`` are present and
    the policy honors them; pass the float through otherwise."""
    if (act_q is None or not _POLICY.act_quant
            or not isinstance(act_q, dict) or site not in act_q):
        return x
    return encode_act(x, act_q[site])


# ----------------------------------------------------------------------
# Einsum canonicalization: spec -> 2-D (optionally batched) matmul plan
# ----------------------------------------------------------------------

class _EinsumPlan(NamedTuple):
    """Label-level plan turning ``einsum(spec, x, w)`` into
    ``[B?, M, K] @ [B?, K, N]`` with reshapes/transposes only (codes are
    moved as bytes, never decoded)."""

    batch: tuple[str, ...]     # labels shared by x, w and out
    xfree: tuple[str, ...]     # labels of M (x and out only)
    contract: tuple[str, ...]  # labels of K (x and w, not out)
    wfree: tuple[str, ...]     # labels of N (w and out only)
    x_perm: tuple[int, ...]    # x transpose -> (batch, xfree, contract)
    w_perm: tuple[int, ...]    # w transpose -> (batch, contract, wfree)
    out_perm: tuple[int, ...]  # (batch, xfree, wfree) -> out label order


@functools.lru_cache(maxsize=None)
def _einsum_plan(spec: str) -> _EinsumPlan | None:
    """Parse a two-operand einsum spec into a matmul plan, or None when
    the spec is not expressible as (batched) ``x @ w``."""
    try:
        operands, out = spec.replace(" ", "").split("->")
        xs, ws = operands.split(",")
    except ValueError:
        return None
    if "." in spec:
        return None
    if len(set(xs)) != len(xs) or len(set(ws)) != len(ws) \
            or len(set(out)) != len(out):
        return None
    batch = tuple(l for l in xs if l in ws and l in out)
    contract = tuple(l for l in xs if l in ws and l not in out)
    xfree = tuple(l for l in xs if l not in ws)
    wfree = tuple(l for l in ws if l not in xs)
    if set(xfree) - set(out) or set(wfree) - set(out):
        return None                   # summed-out free label
    if set(out) != set(batch) | set(xfree) | set(wfree):
        return None
    canonical = batch + xfree + wfree
    return _EinsumPlan(
        batch=batch, xfree=xfree, contract=contract, wfree=wfree,
        x_perm=tuple(xs.index(l) for l in batch + xfree + contract),
        w_perm=tuple(ws.index(l) for l in batch + contract + wfree),
        out_perm=tuple(canonical.index(l) for l in out),
    )


def _maybe_transpose(a: jax.Array, perm: tuple[int, ...]) -> jax.Array:
    if perm == tuple(range(a.ndim)):
        return a
    return jnp.transpose(a, perm)


def _prod(dims) -> int:
    out = 1
    for d in dims:
        out *= int(d)
    return out


def _fused_einsum(x, w: dict, plan: _EinsumPlan, spec: str,
                  cdtype) -> jax.Array:
    """Execute a canonicalized einsum against qtensor codes through the
    fused kernel.  Codes cross as uint8; the decode happens in-kernel.

    ``x`` may itself be an activation :class:`QTensor` — then BOTH
    operands cross as codes and the dual-LUT kernel decodes each
    through its own table (batched specs vmap the dual kernel the same
    way)."""
    from repro.kernels.lut_dequant_matmul import ops as _ops

    codes, qmeta = w["codes"], w["qmeta"]
    x_is_q = isinstance(x, eq.QTensor)
    if x_is_q and not plan.batch and codes.ndim == 2 \
            and plan.w_perm == (1, 0):
        # transposed-codes layout (tied unembedding) has no dual
        # variant: decode the act operand and take the fp-act path
        x = materialize(x, jnp.float32)
        x_is_q = False
    xarr = x.codes if x_is_q else x
    xs, ws = spec.replace(" ", "").split("->")[0].split(",")
    xdims = dict(zip(xs, xarr.shape))
    wdims = dict(zip(ws, codes.shape))
    for l in plan.contract + plan.batch:
        if l in xdims and l in wdims and xdims[l] != wdims[l]:
            raise ValueError(f"dim mismatch for '{l}' in {spec}: "
                             f"{xarr.shape} vs {codes.shape}")
    b_shape = tuple(xdims[l] for l in plan.batch)
    m_shape = tuple(xdims[l] for l in plan.xfree)
    k_shape = tuple(wdims[l] for l in plan.contract)
    n_shape = tuple(wdims[l] for l in plan.wfree)
    b, m, k, n = (_prod(b_shape), _prod(m_shape),
                  _prod(k_shape), _prod(n_shape))

    xt = _maybe_transpose(xarr, plan.x_perm)
    pol = _POLICY
    # A pure 2-D [N, K] -> [K, N] weight swap (tied unembedding) is
    # handled by the kernel's transposed-codes layout: no HBM transpose
    # of the code table, the swap happens on decoded VMEM tiles.
    kernel_transpose = (not plan.batch and codes.ndim == 2
                        and plan.w_perm == (1, 0))
    ct = codes if kernel_transpose else _maybe_transpose(codes, plan.w_perm)
    if x_is_q:
        call = functools.partial(
            _ops.lut_dequant_matmul_dual, qmeta_x=x.qmeta, qmeta_w=qmeta,
            out_dtype=jnp.float32, autotune=pol.autotune)
    else:
        call = functools.partial(
            _ops.lut_dequant_matmul, qmeta=qmeta, out_dtype=jnp.float32,
            autotune=pol.autotune)
    if plan.batch:
        x2 = xt.reshape((b, m, k))
        c2 = ct.reshape((b, k, n))
        out = jax.vmap(lambda a, c: call(a, c))(x2, c2)
    elif kernel_transpose:
        out = call(xt.reshape((m, k)), ct, transpose_codes=True)
    else:
        out = call(xt.reshape((m, k)), ct.reshape((k, n)))
    out = out.reshape(b_shape + m_shape + n_shape)
    out = _maybe_transpose(out, plan.out_perm)
    return out.astype(cdtype)


def dense(x, w, *, dtype=None, epilogue: str | None = None,
          bias=None, out_quant: dict | None = None):
    """``x @ w`` where *either operand* may be quantized.  Contracts the
    last axis of x with the first axis of w.  ``epilogue``/``bias``
    fuse an activation (gelu/silu/relu) and a bias add into the kernel
    flush.

    ``x`` may be an activation :class:`QTensor` — then both operands
    cross HBM as uint8 codes and the dual-LUT kernel decodes each
    in-kernel.  ``out_quant`` (an act-quant site entry
    ``{"lut", "qmeta"}``) turns on the quantize epilogue: the result is
    returned as a :class:`QTensor` re-encoded in-kernel against those
    params, so consecutive quantized matmuls stay code-in/code-out."""
    x_is_q = isinstance(x, eq.QTensor)
    cdtype = dtype or (jnp.float32 if x_is_q else x.dtype)
    if eq.is_qtensor(w):
        if _fused_enabled() and w["codes"].ndim == 2:
            from repro.kernels.lut_dequant_matmul import ops as _ops

            pol = _POLICY
            fuse_ep = pol.fuse_epilogues
            lead = x.shape[:-1]
            n = w["codes"].shape[-1]
            if x_is_q:
                x2 = x.codes.reshape((-1, x.shape[-1]))
                out = _ops.lut_dequant_matmul_dual(
                    x2, w["codes"], x.qmeta, w["qmeta"],
                    epilogue=epilogue if fuse_ep else None,
                    bias=bias if fuse_ep else None,
                    out_qmeta=(out_quant["qmeta"]
                               if out_quant is not None and fuse_ep
                               else None),
                    out_dtype=jnp.float32, autotune=pol.autotune)
                if out_quant is not None and fuse_ep:
                    return eq.QTensor(out.reshape(lead + (n,)),
                                      out_quant["lut"], out_quant["qmeta"])
            else:
                out = _ops.lut_dequant_matmul(
                    x.reshape((-1, x.shape[-1])), w["codes"], w["qmeta"],
                    epilogue=epilogue if fuse_ep else None,
                    bias=bias if fuse_ep else None,
                    out_dtype=jnp.float32, autotune=pol.autotune)
            out = out.reshape(lead + (n,))
            if not fuse_ep:
                out = _epilogue_jnp(out, epilogue, bias)
            out = out.astype(cdtype)
            return _finish_out(out, out_quant)
        wf = materialize(w, cdtype)
        xf = materialize(x, cdtype) if x_is_q else x.astype(cdtype)
        out = jnp.matmul(xf, wf, preferred_element_type=jnp.float32)
        out = _epilogue_jnp(out, epilogue, bias).astype(cdtype)
        return _finish_out(out, out_quant)
    xf = materialize(x, cdtype) if x_is_q else x.astype(cdtype)
    out = jnp.matmul(
        xf, w.astype(cdtype), preferred_element_type=jnp.float32)
    out = _epilogue_jnp(out, epilogue, bias).astype(cdtype)
    return _finish_out(out, out_quant)



def _finish_out(out, out_quant: dict | None):
    """Shared host-side tail: re-encode against the requested output
    params (a :class:`QTensor` comes back) or pass the float through —
    every non-in-kernel-epilogue path in dense/gated_mlp ends here."""
    if out_quant is not None:
        return encode_act(out, out_quant)
    return out


def _epilogue_jnp(out: jax.Array, epilogue: str | None, bias) -> jax.Array:
    from repro.kernels.lut_dequant_matmul.lut_dequant_matmul import (
        apply_activation,
    )

    if bias is not None:
        out = out + bias.astype(out.dtype)
    return apply_activation(out, epilogue)


def dense_general(x, w, contract_spec: str, *, dtype=None) -> jax.Array:
    """Einsum with possibly-quantized operands, e.g. 'bsd,dnh->bsnh'.

    Quantized weights dispatch through the fused kernel for every spec
    the canonicalizer can express as a (batched) 2-D matmul — codes are
    reshaped/byte-transposed, never decoded outside the kernel.  An
    activation :class:`QTensor` rides the same plan: its codes take x's
    transposes/reshapes as bytes and the dual-LUT kernel decodes both
    operands in-kernel."""
    x_is_q = isinstance(x, eq.QTensor)
    cdtype = dtype or (jnp.float32 if x_is_q else x.dtype)
    if eq.is_qtensor(w) and _fused_enabled():
        plan = _einsum_plan(contract_spec)
        if plan is not None and w["codes"].ndim == \
                len(contract_spec.replace(" ", "").split("->")[0]
                    .split(",")[1]):
            return _fused_einsum(x, w, plan, contract_spec, cdtype)
    wf = materialize(w, cdtype)
    xf = materialize(x, cdtype) if x_is_q else x.astype(cdtype)
    return jnp.einsum(
        contract_spec, xf, wf, preferred_element_type=jnp.float32
    ).astype(cdtype)


def gated_mlp(x, w_gate, w_up, activation: str, *,
              dtype=None, out_quant: dict | None = None):
    """``act(x @ w_gate) * (x @ w_up)`` — the gated-MLP front half.

    When both weights are quantized 2-D qtensors, this runs as ONE fused
    dual-matmul kernel (shared x DMA, both decodes in VMEM, the gate
    intermediate never reaches HBM).  An activation :class:`QTensor`
    ``x`` upgrades it to the dual-LUT variant (act codes decoded
    in-kernel too); ``out_quant`` re-encodes the gated flush in-kernel
    and returns a :class:`QTensor`, so the down projection reads codes
    — the MLP intermediate never exists as f32 in HBM.  Falls back to
    two dense calls otherwise."""
    x_is_q = isinstance(x, eq.QTensor)
    cdtype = dtype or (jnp.float32 if x_is_q else x.dtype)
    pol = _POLICY
    if (eq.is_qtensor(w_gate) and eq.is_qtensor(w_up)
            and _fused_enabled() and pol.fuse_epilogues
            and w_gate["codes"].ndim == 2
            and w_gate["codes"].shape == w_up["codes"].shape):
        from repro.kernels.lut_dequant_matmul import ops as _ops

        lead = x.shape[:-1]
        n = w_gate["codes"].shape[-1]
        if x_is_q:
            x2 = x.codes.reshape((-1, x.shape[-1]))
            out = _ops.lut_dequant_matmul_dual_gated(
                x2, w_gate["codes"], w_up["codes"], x.qmeta,
                w_gate["qmeta"], w_up["qmeta"], activation=activation,
                out_qmeta=(out_quant["qmeta"] if out_quant is not None
                           else None),
                out_dtype=jnp.float32, autotune=pol.autotune)
            if out_quant is not None:
                return eq.QTensor(out.reshape(lead + (n,)),
                                  out_quant["lut"], out_quant["qmeta"])
        else:
            out = _ops.lut_dequant_matmul_gated(
                x.reshape((-1, x.shape[-1])), w_gate["codes"],
                w_up["codes"], w_gate["qmeta"], w_up["qmeta"],
                activation=activation, out_dtype=jnp.float32,
                autotune=pol.autotune)
        out = out.reshape(lead + (n,)).astype(cdtype)
        return _finish_out(out, out_quant)
    g = dense(x, w_gate, dtype=cdtype, epilogue=activation)
    out = (g * dense(x, w_up, dtype=cdtype)).astype(cdtype)
    return _finish_out(out, out_quant)


def embed_lookup(w, idx: jax.Array, dtype) -> jax.Array:
    """Embedding-table row gather that never decodes the full table:
    for qtensors, gather uint8 code rows first, then map through the
    256-entry LUT (bytes cross HBM, not the bf16 table)."""
    if eq.is_qtensor(w):
        rows = jnp.take(w["codes"], idx, axis=0).astype(jnp.int32)
        return jnp.take(w["lut"].astype(dtype), rows, axis=0)
    return w.astype(dtype)[idx]


# ----------------------------------------------------------------------
# Tree-level quantization
# ----------------------------------------------------------------------

# weights consumed through dense()/materialize() — safe to quantize.
_QUANT_NAMES = {"out", "tokens", "enc_in"}
# routing/modulation weights: numerically load-bearing far beyond their
# size (router flips top-k experts; LoRA adjusters modulate token-shift
# interpolants) — production quantization recipes keep these fp, and so
# does the paper's >=99%-accuracy constraint in practice.
_QUANT_SKIP = {"router", "lora_a", "lora_b", "decay_a", "decay_b", "wkv"}


def default_predicate(path: tuple, leaf) -> bool:
    """Quantize matmul weights only (the paper quantizes FC/GEMM weights,
    §V-A): leaves named w* or in the known projection set.  Parameters
    used via direct arithmetic (token-shift mus, decays, norms, conv
    taps) and routing/modulation weights stay fp."""
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    if not jnp.issubdtype(leaf.dtype, jnp.floating):
        return False
    name = str(path[-1]).lower()
    if name in _QUANT_SKIP:
        return False
    if name in _QUANT_NAMES:
        return True
    return name.startswith("w") and "conv" not in name


def _path_str(path) -> tuple:
    out = []
    for p in path:
        out.append(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("bits", "lut_dtype"))
def _quantize_leaf(x, bits: int, lut_dtype):
    """Fit, encode and pack one tensor as one program: run op by op, the
    encode and SQNR passes would each hold full-size temporaries at once
    (gigabytes for an embedding table, next to the f32 model)."""
    x = x.astype(jnp.float32)
    qp = eq.fit(x, bits)
    return eq.pack_qtensor(eq.encode(x, qp), qp, lut_dtype), eq.sqnr_db(x, qp)


def _quantize_stacked(leaf, bits: int, lut_dtype):
    """Per-layer DNA-TEQ fit for scan-stacked weights [L, ...]: one
    quantizer per layer (faithful to the paper's per-layer precision),
    packed with leading L on every field so lax.scan slices cleanly."""
    # layer by layer (not vmapped) so the fit's temporaries are one
    # layer's worth, not the whole stack's
    packed, sqnrs = jax.lax.map(
        lambda x: _quantize_leaf(x, bits, lut_dtype), leaf)
    return packed, float(jnp.mean(sqnrs))


def quantize_tree(
    params,
    bits: int = 7,
    predicate: Callable = default_predicate,
    lut_dtype=jnp.float32,
    axes=None,
):
    """Replace eligible weight leaves with qtensor dicts (fit per tensor;
    per *layer* for scan-stacked weights when ``axes`` marks a leading
    "layers" dim).  Returns (new_params, report{path: (bits, sqnr_db)}).
    """
    report = {}
    axes_leaves = {}
    if axes is not None:
        flat = jax.tree_util.tree_flatten_with_path(
            axes, is_leaf=lambda x: isinstance(x, tuple))[0]
        for path, ax in flat:
            axes_leaves[_path_str(path)] = ax

    def visit(path, leaf):
        key = _path_str(path)
        if eq.is_qtensor(leaf) or not predicate(key, leaf):
            return leaf
        ax = axes_leaves.get(key)
        if ax and len(ax) and ax[0] == "layers":
            packed, sqnr = _quantize_stacked(leaf, bits, lut_dtype)
            report[key] = (bits, sqnr)
            return packed
        packed, sqnr = _quantize_leaf(leaf, bits, lut_dtype)
        report[key] = (bits, float(sqnr))
        return packed

    new = jax.tree_util.tree_map_with_path(visit, params)
    return new, report


def quantize_tree_mixed(
    params,
    min_sqnr_db: float = 22.0,
    predicate: Callable = default_predicate,
    lut_dtype=jnp.float32,
    axes=None,
):
    """DNA-TEQ mixed-precision variant: per-tensor bitwidth search
    (paper Table VI).  For scan-stacked weights the width is searched on
    layer 0 and the per-layer fit applied at that width.  Returns
    (new_params, report{path: (bits, sqnr)})."""
    report = {}
    axes_leaves = {}
    if axes is not None:
        flat = jax.tree_util.tree_flatten_with_path(
            axes, is_leaf=lambda x: isinstance(x, tuple))[0]
        for path, ax in flat:
            axes_leaves[_path_str(path)] = ax

    def visit(path, leaf):
        key = _path_str(path)
        if eq.is_qtensor(leaf) or not predicate(key, leaf):
            return leaf
        ax = axes_leaves.get(key)
        if ax and len(ax) and ax[0] == "layers":
            bits, _ = eq.search_bitwidth(
                leaf[0].astype(jnp.float32), min_sqnr_db)
            packed, sqnr = _quantize_stacked(leaf, bits, lut_dtype)
            report[key] = (bits, sqnr)
            return packed
        bits, qp = eq.search_bitwidth(leaf.astype(jnp.float32), min_sqnr_db)
        codes = eq.encode(leaf.astype(jnp.float32), qp)
        report[key] = (bits, float(eq.sqnr_db(leaf, qp)))
        return eq.pack_qtensor(codes, qp, lut_dtype)

    new = jax.tree_util.tree_map_with_path(visit, params)
    return new, report


def abstract_quantize(aparams, axes, bits: int = 7, lut_dtype=jnp.float32,
                      predicate: Callable = default_predicate):
    """Shape-only mirror of :func:`quantize_tree` for dry-run lowering:
    eligible weight ShapeDtypeStructs become {codes: uint8, lut, qmeta}
    struct dicts (per-layer tables for scan-stacked weights).  Returns
    (abstract_qparams, qaxes) where qaxes extends the logical-axes tree.
    """
    flat_axes = {}
    flat = jax.tree_util.tree_flatten_with_path(
        axes, is_leaf=lambda x: isinstance(x, tuple))[0]
    for path, ax in flat:
        flat_axes[_path_str(path)] = ax

    def visit(path, leaf):
        key = path  # plain string tuple
        if not predicate(key, leaf):
            return leaf, flat_axes.get(key)
        ax = flat_axes.get(key) or (None,) * len(leaf.shape)
        stacked = len(ax) > 0 and ax[0] == "layers"
        lead = (leaf.shape[0],) if stacked else ()
        lead_ax = ("layers",) if stacked else ()
        q = {
            "codes": jax.ShapeDtypeStruct(leaf.shape, jnp.uint8),
            "lut": jax.ShapeDtypeStruct(lead + (256,), lut_dtype),
            "qmeta": jax.ShapeDtypeStruct(lead + (4,), jnp.float32),
        }
        qa = {
            "codes": ax,
            "lut": lead_ax + (None,),
            "qmeta": lead_ax + (None,),
        }
        return q, qa

    # recursive structural walk (preserves empty subtrees, e.g. the
    # parameter-free non-parametric LayerNorm dicts of olmo)
    def walk(node, path):
        if isinstance(node, dict) and not (
                jax.tree_util.all_leaves([node]) if node else False):
            p_out, a_out = {}, {}
            for k, v in node.items():
                p_out[k], a_out[k] = walk(v, path + (k,))
            return p_out, a_out
        q, qa = visit(path, node)
        if qa is None:
            qa = flat_axes.get(path)
        return q, qa

    out_p, out_a = {}, {}
    for k, v in aparams.items():
        out_p[k], out_a[k] = walk(v, (k,))
    return out_p, out_a


def quantized_fraction(params) -> float:
    """Fraction of parameter *bytes* now held as uint8 codes."""
    q = tot = 0
    for leaf in jax.tree_util.tree_leaves(
        params, is_leaf=eq.is_qtensor
    ):
        if eq.is_qtensor(leaf):
            n = int(leaf["codes"].size)
            q += n
            tot += n
        elif hasattr(leaf, "size"):
            tot += int(leaf.size)
    return q / max(tot, 1)


def avg_bits(report: dict) -> float:
    """Average searched exponent bitwidth (compare Table VI 'Avg bit')."""
    if not report:
        return 0.0
    return sum(b for b, _ in report.values()) / len(report)
