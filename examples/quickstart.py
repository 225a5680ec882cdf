"""Quickstart: the paper's technique end to end in 60 lines.

1. DNA-TEQ-quantize a weight matrix (sign + integer exponent codes),
2. compute a matmul three ways — float reference, the paper's Eq.1
   counting formulation, and the TPU-native fused LUT-dequant kernel —
   and show they agree,
3. run the Lama bulk-multiplication LUT op (case study 1) and the
   command-level PIM cost model that reproduces Table V.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import exponential_quant as eq
from repro.core import exponent_dotprod as ed
from repro.core.pim import lama_bulk_cost, pluto_bulk_cost
from repro.kernels.lama_bulk_op import lama_vector_matrix
from repro.kernels.lut_dequant_matmul import lut_dequant_matmul

rng = np.random.default_rng(0)

# --- 1. quantize ---------------------------------------------------------
w = jnp.asarray(rng.normal(size=(256, 384)) * 0.05, jnp.float32)
x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
codes, qp = eq.quantize(w, bits=6)
print(f"quantized 256x384 weight to 6-bit exponents: "
      f"alpha={float(qp.alpha):.4f} beta={float(qp.beta):.4f} "
      f"base={float(qp.base):.4f}  SQNR={float(eq.sqnr_db(w, qp)):.1f} dB")

# --- 2. three ways to multiply -------------------------------------------
ref = x @ w
deq = ed.dequant_matmul(
    eq.encode(x, eq.fit(x, 7)), eq.fit(x, 7), codes, qp)  # both quantized
kern = lut_dequant_matmul(x, codes, eq.pack_qmeta(qp),
                          out_dtype=jnp.float32)           # activations fp
count = ed.counting_dot(
    eq.encode(x[0], qp_x := eq.fit(x[0], 7)), qp_x,
    eq.encode(w[:, 0], qp_w := eq.ExpQuantParams(
        eq.fit(w[:, 0], 6).alpha, eq.fit(w[:, 0], 6).beta, qp_x.base, 6)),
    qp_w)
print(f"float x@w[0,0]        = {float(ref[0, 0]):+.5f}")
print(f"fused LUT kernel      = {float(kern[0, 0]):+.5f}  "
      f"(weights as codes, decode fused into the MXU matmul)")
print(f"Eq.1 counting dot     = {float(count):+.5f}  "
      f"(signed exponent histograms, the LamaAccel mechanism)")

# --- 3. Lama case study 1: bulk LUT multiplication -----------------------
v = jnp.asarray(rng.integers(0, 16, 8), jnp.int32)
m = jnp.asarray(rng.integers(0, 16, (8, 128)), jnp.int32)
out = lama_vector_matrix(v, m, bits=4)
assert bool(jnp.all(out == v @ m)), "LUT vector-matrix must be exact"
print("\nLama bulk 4-bit vector-matrix via scalar-prefetch LUT rows: exact")

lama = lama_bulk_cost(1024, 8)
pluto = pluto_bulk_cost(1024, 8)
print(f"PIM model, 1024 INT8 muls:  Lama {lama.latency_ns:.0f} ns / "
      f"{lama.energy_nj:.1f} nJ / {lama.counts.act} ACTs   vs  "
      f"pLUTo {pluto.latency_ns:.0f} ns / {pluto.energy_nj:.1f} nJ / "
      f"{pluto.counts.act} ACTs")
print(f"-> {pluto.latency_ns/lama.latency_ns:.1f}x faster, "
      f"{pluto.energy_nj/lama.energy_nj:.1f}x less energy (paper: 3.5x/8.3x)")
