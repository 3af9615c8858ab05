#!/usr/bin/env python3
"""Per-bucket timing on a TPU of causal prefill attention: the plain XLA
path (``models.layers._sdpa`` under a causal mask) against the Pallas flash
kernel with row lengths, at the served oracles' head shapes.

    python3 scripts/attn_bucket_timing.py [--out FILE] [--reps N]

For each configuration, bucket and kernel tiling it times one jitted
program that runs the attention of ``--reps`` layers back to back (each
layer's output is the next one's query), from the (B, S, heads, hd) layout
of the projections to the (B, S, H*hd) input of the output projection, and
reports milliseconds per layer (median of 5 timed calls, after a warm-up
call).  Row lengths are drawn as a bucket holds them: uniform over (L/2, L]
(over [1, 32] for the 32 bucket), and every row full as the worst case.
The largest gap of the kernel's output from plain's over real rows is
printed beside each time.  One JSON line a bucket goes to stdout, and
the whole as one document to ``--out`` if given.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.kernels.flash_attention.kernel import flash_attention_pallas  # noqa: E402
from repro.models.layers import _causal_window_mask, _sdpa  # noqa: E402

# (name, heads, kv heads, head_dim, buckets) of the served oracles, batch 16
SHAPES = [("qwen1.5-0.5b", 16, 16, 64, [32, 64, 128, 256, 512, 1024, 2048]),
          ("codeqwen1.5-7b", 32, 4, 128, [32, 64, 128, 256, 512])]
BATCH = 16
# (block_q, block_k, block_h) tried per shape
TILINGS = {"qwen1.5-0.5b": [(128, 128, 16), (128, 128, 8), (256, 128, 16),
                            (128, 256, 16), (256, 256, 16), (256, 256, 8),
                            (512, 256, 8)],
           "codeqwen1.5-7b": [(128, 128, 8), (128, 128, 16), (128, 128, 32),
                              (256, 128, 8), (128, 256, 8), (256, 256, 8)]}


def plain(q, k, v):
    """q (B,S,KV,G,hd), k/v (B,S,KV,hd) -> (B,S,H*hd), as attention_plain."""
    B, S = q.shape[:2]
    pos = jnp.arange(S)[None, :]
    mask = _causal_window_mask(pos, pos, None)[:, None, None]
    out = _sdpa(q, k, v, mask, 1.0 / math.sqrt(q.shape[-1]))
    return out.reshape(B, S, -1)


def flash(q, k, v, lens, *, block_q, block_k, block_h):
    """The same from the same layout, through the kernel: heads to the
    front for it, and back."""
    B, S, KV, G, hd = q.shape
    qh = q.reshape(B, S, KV * G, hd).transpose(0, 2, 1, 3)
    out = flash_attention_pallas(qh, k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3), lens,
                                 block_q=block_q, block_k=block_k,
                                 block_h=block_h)
    return out.transpose(0, 2, 1, 3).reshape(B, S, -1)


def stacked(attn, reps):
    def run(q, k, v, *extra):
        def body(q, _):
            return attn(q, k, v, *extra).reshape(q.shape), None
        q, _ = jax.lax.scan(body, q, None, length=reps)
        return q
    return jax.jit(run)


def time_ms(fn, args, reps):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(ts) / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", help="also write every row as one JSON file")
    ap.add_argument("--reps", type=int, default=12)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("attn_bucket_timing: needs a TPU")
    rng = np.random.default_rng(0)
    rows = []
    for name, H, KV, hd, buckets in SHAPES:
        G = H // KV
        for L in buckets:
            ks = jax.random.split(jax.random.key(L), 3)
            q = jax.random.normal(ks[0], (BATCH, L, KV, G, hd), jnp.bfloat16)
            k = jax.random.normal(ks[1], (BATCH, L, KV, hd), jnp.bfloat16)
            v = jax.random.normal(ks[2], (BATCH, L, KV, hd), jnp.bfloat16)
            lo = 1 if L <= 32 else L // 2 + 1
            lens_b = jnp.asarray(np.sort(rng.integers(lo, L + 1, BATCH)),
                                 jnp.int32)
            lens_f = jnp.full((BATCH,), L, jnp.int32)
            row = {"config": name, "bucket": L,
                   "lens_mean": float(lens_b.mean()),
                   "plain_ms": time_ms(stacked(plain, args.reps), (q, k, v),
                                       args.reps)}
            ref = np.asarray(plain(q, k, v), np.float32)
            real = (np.arange(L)[None, :] < np.asarray(lens_b)[:, None])
            for bq, bk, bh in TILINGS[name]:
                tag = f"{min(bq, L)}x{min(bk, L)}h{bh}"
                if tag in row:
                    continue
                f = lambda q, k, v, n: flash(q, k, v, n, block_q=bq,  # noqa: E731
                                             block_k=bk, block_h=bh)
                try:
                    got = np.asarray(f(q, k, v, lens_b), np.float32)
                    err = float(np.abs(got - ref)[real].max())
                    sf = stacked(f, args.reps)
                    row[tag] = {
                        "ms": time_ms(sf, (q, k, v, lens_b), args.reps),
                        "ms_full": time_ms(sf, (q, k, v, lens_f), args.reps),
                        "max_err": err}
                except Exception as e:  # a tiling the chip refuses
                    row[tag] = {"error": str(e).splitlines()[0][:200]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps({"device": dev.device_kind, "batch": BATCH,
                                   "reps": args.reps, "rows": rows},
                                  indent=1))


if __name__ == "__main__":
    main()
