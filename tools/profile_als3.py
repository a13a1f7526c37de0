"""Profiler round 3 (fixed): per-kernel cost via in-program iteration
deltas. Each iteration's result enters a FULL reduction (`.sum()`), so
XLA cannot dead-code-eliminate any of the kernel, and every timed call
gets a perturbed input."""
import time

import numpy as np
import jax
import jax.numpy as jnp

nnz, U, rank = 1_000_000, 6040, 10
K = rank * rank + rank + 1
k0 = jax.random.PRNGKey(0)
contrib = jax.random.uniform(k0, (nnz, K), jnp.float32)
x = jax.random.uniform(k0, (nnz, rank), jnp.float32)
ids = jnp.clip(jnp.arange(nnz, dtype=jnp.int32) // (nnz // U), 0, U - 1)
starts = jnp.arange(U, dtype=jnp.int32) * (nnz // U)
ends = starts + nnz // U
A0 = jax.random.uniform(k0, (U, rank, rank), jnp.float32)
Amat = jnp.einsum("nij,nkj->nik", A0, A0) + 10 * jnp.eye(rank)
bvec = jax.random.uniform(k0, (U, rank), jnp.float32)
C = 512
Lb = -(-nnz // C)
pad = Lb * C - nnz


def kernel_delta(name, body, arg, iters=8, reps=3):
    def many(n):
        def f(a, i):
            return jnp.asarray(body(a + i * 1e-7)).sum()
        return jax.jit(lambda a: jax.lax.fori_loop(
            0, n, lambda i, s: s + f(a, i), jnp.asarray(0.0)))

    g1, gn = many(1), many(1 + iters)
    np.asarray(g1(arg)); np.asarray(gn(arg))          # compile both
    t1, tn = [], []
    for _ in range(reps):
        t0 = time.perf_counter(); np.asarray(g1(arg))
        t1.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); np.asarray(gn(arg))
        tn.append(time.perf_counter() - t0)
    dt = (min(tn) - min(t1)) / iters
    print(f"{name:44s} {dt*1e3:8.2f} ms", flush=True)


def blocks(c):
    cpad = jnp.concatenate([c, jnp.zeros((pad, K), c.dtype)])
    return cpad.reshape(Lb, C, K)


def bsum(c):
    return blocks(c).sum(axis=1)


def intra(c):
    return jnp.cumsum(blocks(c), axis=1)


def twolevel_f64(c):
    it = jnp.cumsum(blocks(c), axis=1)
    with jax.enable_x64(True):
        bs = it[:, -1, :].astype(jnp.float64)
        inter = jnp.concatenate(
            [jnp.zeros((1, K), jnp.float64), jnp.cumsum(bs, axis=0)])

        def prefix(t):
            bi, ri = t // C, t % C
            part = jnp.where((ri > 0)[:, None], it[bi, ri - 1], 0.0)
            return inter[bi] + part.astype(jnp.float64)

        return (prefix(ends) - prefix(starts)).astype(c.dtype)


def centered_f32(c):
    blk = blocks(c)
    mean = blk.sum(axis=1).sum(axis=0) / (Lb * C)
    it = jnp.cumsum(blk - mean, axis=1)
    inter = jnp.concatenate(
        [jnp.zeros((1, K), jnp.float32), jnp.cumsum(it[:, -1, :], axis=0)])

    def prefix(t):
        bi, ri = t // C, t % C
        return inter[bi] + jnp.where((ri > 0)[:, None], it[bi, ri - 1], 0.0)

    span = (ends - starts).astype(jnp.float32)[:, None]
    return (prefix(ends) - prefix(starts)) + mean * span


def build_contrib(xa):
    return jnp.concatenate(
        [(xa[:, :, None] * xa[:, None, :]).reshape(-1, rank * rank),
         xa, jnp.ones((nnz, 1), xa.dtype)], axis=1)


def solve(c):
    A2 = Amat + c.ravel()[0] * 1e-9
    return jnp.linalg.solve(A2, bvec[..., None])[..., 0]


def gj(c):
    A2 = Amat + c.ravel()[0] * 1e-9
    M = jnp.concatenate(
        [A2, jnp.broadcast_to(jnp.eye(rank, dtype=A2.dtype), A2.shape)], -1)
    for i in range(rank):
        piv = M[:, i, :] / M[:, i, i:i + 1]
        M = M - M[:, :, i:i + 1] * piv[:, None, :]
        M = M.at[:, i, :].set(piv)
    return jnp.einsum("nij,nj->ni", M[:, :, rank:], bvec)


def scatter(c):
    return jnp.zeros((U, K), jnp.float32).at[ids[:U]].add(c[:U])


kernel_delta("build contrib (outer+concat)", build_contrib, x)
kernel_delta("block sums", bsum, contrib)
kernel_delta("intra cumsum", intra, contrib)
kernel_delta("full twolevel f64", twolevel_f64, contrib)
kernel_delta("centered all-f32", centered_f32, contrib)
kernel_delta("scatter-add (U rows)", scatter, contrib)
kernel_delta("linalg.solve (U,10,10)", solve, contrib)
kernel_delta("gauss-jordan (U,10,10)", gj, contrib)
print("done", flush=True)
