"""Per-call device time of the query programs' compactions.

    python3 benchmarks/bench_compact.py [--out <path.json>] [--smoke]

Times `core.serve._compact`, called as the count / range programs call
it (candidate pages over a (16, P) mask; hits over a (16, C * 1024) mask
with page tags), against the scatter formulation it replaced, at the
ladder's rungs on the 10M-row OSM index (10,752 padded pages, cap 1024,
16-query chunks) and the store engine's 64-page groups (65,536 pages).
Each timing is a device loop of many calls over a mask that changes per
call, so dispatch and compilation fall outside it.  One JSON object per
line: shape, rung, variant, ms per call, and the device it ran on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.serve import _compact  # noqa: E402

R, CAP, PAGES = 16, 1024, 10_752
# (name, mask width N, output width, candidate pages C for hits or 0)
RUNGS = [("count_c64", PAGES, 64, 0), ("count_c4096", PAGES, 4096, 0),
         ("store_c64", 65_536, 64, 0),
         ("range_c64_h1024", 64 * CAP, 1024, 64),
         ("range_c256_h4096", 256 * CAP, 4096, 256),
         ("range_c1024_h16384", 1024 * CAP, 16_384, 1024),
         ("range_c4096_h65536", 4096 * CAP, 65_536, 4096)]
SMOKE = [("count_smoke", 300, 16, 0), ("range_smoke", 4 * CAP, 64, 4)]


def scatter_ids(mask, width, gid):
    """The replaced formulation: one masked scatter of every entry."""
    pos = jnp.cumsum(mask, axis=1) - 1
    ok = mask & (pos < width)
    q = jnp.broadcast_to(jnp.arange(R)[:, None], mask.shape)
    out = jnp.full((R, width), -1, jnp.int32)
    out = out.at[jnp.where(ok, q, R), jnp.where(ok, pos, 0)].set(
        gid, mode="drop")
    return out, pos[:, -1] + 1


def variants(N, width, C):
    iota = jnp.arange(N, dtype=jnp.int32)[None, :]
    if not C:
        return {"scatter": lambda m, cand: scatter_ids(m, width, iota),
                "compact": lambda m, cand: _compact(m, width)}

    def compact(m, cand):
        pos, n, page = _compact(m, width, tags=jnp.repeat(
            cand, CAP // 128, axis=1))
        return jnp.where(pos >= 0, page * CAP + pos % CAP, -1), n

    def scatter(m, cand):
        gid = (cand[:, :, None] * CAP
               + jnp.arange(CAP, dtype=jnp.int32)).reshape(R, N)
        return scatter_ids(m, width, gid)
    return {"scatter": scatter, "compact": compact}


def per_call_ms(f, mask, cand) -> float:
    @jax.jit
    def loop(mask, cand, reps):
        flip = jnp.arange(mask.shape[1])[None, :]

        def body(i, acc):
            ids, n = f(mask ^ (flip == i), cand)
            return acc + jnp.sum(ids) + jnp.sum(n)
        return jax.lax.fori_loop(0, reps, body, jnp.int32(0))

    loop(mask, cand, 1).block_until_ready()          # compile
    t = time.perf_counter()
    loop(mask, cand, 1).block_until_ready()
    reps = int(max(2, min(2000, 0.4 / max(time.perf_counter() - t, 1e-6))))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        loop(mask, cand, reps).block_until_ready()
        best = min(best, (time.perf_counter() - t) / reps)
    return 1e3 * best


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--smoke", action="store_true",
                    help="two tiny shapes (a CPU check of the script)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    rows = []
    for name, N, width, C in SMOKE if args.smoke else RUNGS:
        rng = np.random.default_rng(N + width)
        mask = jnp.asarray(rng.random((R, N)) < min(1.0, 0.8 * width / N))
        cand = jnp.asarray(np.sort(np.stack(
            [rng.choice(PAGES, max(C, 1), replace=False) for _ in range(R)]),
            axis=1).astype(np.int32))
        fs = variants(N, width, C)
        want, got = (jax.jit(f)(mask, cand) for f in fs.values())
        for w, g in zip(want, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        for variant, f in fs.items():
            row = {"shape": name, "N": N, "width": width, "variant": variant,
                   "ms": per_call_ms(f, mask, cand),
                   "device": dev.device_kind, "platform": dev.platform}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write("".join(json.dumps(r) + "\n" for r in rows))


if __name__ == "__main__":
    main()
