"""On-device reductions over the mesh data axis.

``mesh_reduce_stats`` is the device path of ``risk_accumulate`` (BASELINE.json
north star: "risk_accumulate runs as an on-device lax.psum reduction",
replacing the reference's host-side ``sum``/``min``/``max``, reference
``ops/risk_accumulate.py:65-68``): values are sharded over ``dp``, each shard
reduces locally on its chip, and the partials combine over ICI with
``lax.psum``/``pmin``/``pmax`` inside a ``shard_map``.

Shape discipline: input length is padded up to a power-of-two multiple of the
dp axis size with a mask, so the executable cache sees a small set of static
lengths (same bucketing story as ``pad_batch``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _padded_len(n: int, multiple: int) -> int:
    """Smallest power-of-two bucket ≥ n that is a multiple of ``multiple``."""
    size = max(multiple, 1)
    while size < n:
        size *= 2
    return size


def _build_stats_fn(runtime) -> Any:
    mesh = runtime.mesh

    def local_stats(hi: jax.Array, lo: jax.Array, m: jax.Array):
        # Double-single sum: hi/lo are the f32 split of the f64 inputs (hi =
        # round(v), lo = v - hi), so the sum of BOTH partial sums recovers the
        # f64 values' sum up to f32 *accumulation* error — the input-cast
        # error of a plain f32 path is gone entirely. The two partials
        # combine on the host in f64 (see mesh_reduce_stats).
        s_hi = lax.psum(jnp.sum(hi * m), "dp")
        s_lo = lax.psum(jnp.sum(lo * m), "dp")
        # min/max via monotone bitcast keys, reduced as *integers*.  A float
        # pmin/pmax on the VPU flushes subnormal inputs to zero (FTZ), which
        # broke the exact-f32 contract for inputs like 1.4e-45 (round-4
        # Hypothesis counterexample).  The IEEE-754 sign-magnitude encoding
        # admits a monotone map to uint32 — key = bits ^ (0x80000000 for
        # positives, 0xFFFFFFFF for negatives) — so integer reductions order
        # floats exactly, subnormals included: bitcast, xor, and integer
        # min/max never touch the float datapath, so nothing can flush.
        bits = lax.bitcast_convert_type(hi, jnp.uint32)
        key = bits ^ jnp.where(
            (bits >> 31) != 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0x80000000)
        )
        # Pad sentinels: 0xFFFFFFFF is the largest key (above +inf's), 0 the
        # smallest (below -inf's); n ≥ 1 guarantees a real element survives.
        k_mn = lax.pmin(
            jnp.min(jnp.where(m > 0, key, jnp.uint32(0xFFFFFFFF))), "dp"
        )
        k_mx = lax.pmax(jnp.max(jnp.where(m > 0, key, jnp.uint32(0))), "dp")
        return s_hi, s_lo, k_mn, k_mx

    fn = jax.shard_map(
        local_stats,
        mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp")),
        out_specs=(P(), P(), P(), P()),
    )
    return jax.jit(fn)


def mesh_reduce_stats(runtime, values: Sequence[float]) -> Dict[str, Any]:
    """count/sum/mean/min/max of ``values``, reduced on-device over ``dp``.

    Returns the ``risk_accumulate`` result fields (reference
    ``ops/risk_accumulate.py:70-77`` shape); the caller adds ``ok``/timing.

    Numerics contract: inputs ship as a double-single (hi/lo f32) pair, so
    there is NO input-cast error vs the host ``math.fsum`` path for the
    **sum** (the residual is f32 *accumulation* error of the shard-local
    sums, worst-case relative ``n · 2⁻²⁴`` and in practice far smaller — XLA
    reduces in trees). **min/max equal the f32 rounding of the exact f64
    extremes — an equality, not a tolerance, subnormals included**: rounding
    is monotone, so ``min(round(v)) == round(min(v))``, and the reduction
    runs over monotone bitcast integer keys (see ``_build_stats_fn``) so the
    device's flush-to-zero float mode cannot perturb it. The controller-side
    merge path stays exact (``risk_accumulate`` host fsum); the sum here
    trades the last-ulp accumulation exactness for on-chip reduction over
    ICI.
    """
    n = len(values)
    if n == 0:
        return {"count": 0, "sum": 0.0, "mean": 0.0, "min": None, "max": None}
    if np.isnan(values).any():
        # NaN poisons every statistic, deterministically. Without this check
        # the bitcast-key reduce would apply IEEE total-order semantics
        # (negative NaN < -inf, positive NaN > +inf) — order-independent but
        # asymmetric (min skips a positive NaN that max returns) — and the
        # host path's Python ``min``/``max`` are order-DEPENDENT under NaN,
        # so neither is a contract worth matching. ``fsum`` already yields
        # NaN for the sum; min/max follow it. (Same canonicalization in the
        # ``risk_accumulate`` host path.)
        nan = float("nan")
        return {"count": n, "sum": nan, "mean": nan, "min": nan, "max": nan}
    dp = runtime.axis_size("dp")
    size = _padded_len(n, dp)
    v64 = np.zeros(size, dtype=np.float64)
    v64[:n] = np.asarray(values, dtype=np.float64)
    # Values beyond f32 range cast to ±inf; their residual would be ∓inf and
    # the recombined sum inf + -inf = NaN. Zero the residual instead so the
    # overflow stays a detectable inf, same as a plain f32 cast. Both the
    # overflowing cast and the inf arithmetic are this function's documented
    # behavior, not accidents — silence numpy's warnings for exactly that.
    with np.errstate(over="ignore", invalid="ignore"):
        hi = v64.astype(np.float32)
        lo = np.where(
            np.isfinite(hi), v64 - hi.astype(np.float64), 0.0
        ).astype(np.float32)
    m = np.zeros(size, dtype=np.float32)
    m[:n] = 1.0

    fn = runtime.compiled(
        ("mesh_reduce_stats", size, dp), lambda: _build_stats_fn(runtime)
    )
    sharding = runtime.sharding("dp")
    s_hi, s_lo, k_mn, k_mx = fn(
        jax.device_put(hi, sharding),
        jax.device_put(lo, sharding),
        jax.device_put(m, sharding),
    )
    # count is exact host knowledge (len), not a float32 mask-psum: a mask sum
    # loses integer exactness past 2^24 elements. The hi/lo partials combine
    # here in f64 — the whole point of shipping the split.
    total = float(s_hi) + float(s_lo)
    return {
        "count": n,
        "sum": total,
        "mean": total / n,
        "min": _key_to_f32(int(k_mn)),
        "max": _key_to_f32(int(k_mx)),
    }


def _key_to_f32(key: int) -> float:
    """Invert the monotone uint32 order key back to its f32 value (host side,
    pure integer ops — the device never reconstructs the float)."""
    bits = key ^ (0x80000000 if key & 0x80000000 else 0xFFFFFFFF)
    return float(np.uint32(bits).view(np.float32))
