"""Multilayer perceptron objective — TPU-native.

Re-design of the reference ann/ package (23 files, 1,174 LoC:
FeedForwardTopology.multiLayerPerceptron, AffineLayer, SigmoidFunction,
SoftmaxLayerWithCrossEntropyLoss, Stacker, AnnObjFunc): all weights are
flattened into ONE coefficient vector (the Stacker contract) so the MLP
plugs into the same distributed L-BFGS engine as the linear models
(MultilayerPerceptronTrainBatchOp.java:146-147).

Two forms of the table. Host rows ``(n, d)`` (already standardized) take
``jax.grad`` over the shard (``calc_grad_shard``, ``line_losses_shard``).
A ``DenseBlockColumn`` (floats, or one byte a value) trains where it lies,
as the linear objectives' blocked table does (``optim/objfunc.py``): each
superstep WALKS it twice, block by block (``grad_pass``: forward and
hand-written backward; ``line_pass``: the line search's ladder), the
standardization folded into layer 1 (``fold_coef`` / ``unfold_grad``), the
blocks' sums Kahan-added, the rows counted as whole numbers. Activations
are feature-major, ``(units, rows)``. The bytes meet layer 1 as exact
bfloat16 against ``split3`` of the weights, float32 grade: on a table of
bytes where Pallas runs, as the kernels of ``kernels/mlp.py`` (the block
read where it lies and widened once, the parts' products joined in VMEM),
else as ``block_forward`` / ``block_backward`` (:func:`walk_path` reads
which from the table, nothing sets it); every product of two floats
(forward, backward, along the ladder) is at matmul precision ``highest``
(:func:`dot_highest`).
Only layer 1 is linear in the step, ``z1(a) = z1 - a (X . D1 + d_b1)``, so
the line pass takes ONE product of the stacked ``[W1; D1]`` against a block
and runs the ladder's rungs from there: where Pallas runs, as ONE kernel a
block (``kernels/mlp_rungs.py``: a tile of ``z1`` and ``zd1`` read once,
every rung run on it), else as a ``lax.map`` of a rung (:func:`rung_path`
reads which from the input, nothing sets it).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import DenseBlockColumn
from ....kernels import mlp as layer1
from ....kernels import mlp_rungs
from ..blocked import block_at
from ..optim.objfunc import (OptimObjFunc, _walk, block_backward,
                             block_forward, fold_coef, pack_dense,
                             unfold_grad)

#: the engine names the compiled step program ``jit_<first word of its key>``
MLP_QN_PROGRAM = "mlp_qn"


def stack_sizes(layer_sizes: Sequence[int]) -> int:
    """Total flattened parameter count (reference Stacker)."""
    total = 0
    for a, b in zip(layer_sizes[:-1], layer_sizes[1:]):
        total += a * b + b
    return total


def unstack(coef, layer_sizes: Sequence[int]) -> List[Tuple]:
    """coef -> [(W (in,out), b (out,)), ...]."""
    out = []
    pos = 0
    for a, b in zip(layer_sizes[:-1], layer_sizes[1:]):
        W = coef[pos:pos + a * b].reshape(a, b)
        pos += a * b
        bias = coef[pos:pos + b]
        pos += b
        out.append((W, bias))
    return out


def mlp_forward(coef, X, layer_sizes: Sequence[int]):
    """Logits of the final layer; sigmoid hidden activations (reference
    SigmoidFunction between AffineLayers)."""
    h = X
    layers = unstack(coef, layer_sizes)
    for i, (W, b) in enumerate(layers):
        z = h @ W + b
        h = z if i == len(layers) - 1 else jax.nn.sigmoid(z)
    return h


def walk_path(X, layer_sizes: Sequence[int]) -> str:
    """Which walk layer 1 of the blocked passes takes over a table ``X``
    (blocks ``(nbl, d, S, 128)``, or anything that has their ``dtype``
    and ``shape``): ``"kernel"`` (``kernels/mlp.py``) or ``"xla"``
    (``block_forward`` / ``block_backward``), read from the input for the
    line pass's stacked ``[W1; D1]``, the larger product."""
    return layer1.walk_path(X.dtype, X.shape[1], X.shape[2],
                            2 * layer_sizes[1])


def rung_path(X, layer_sizes: Sequence[int], dtype) -> str:
    """Which path the line pass's ladder takes over a table ``X`` (blocks
    ``(nbl, d, S, 128)``, or anything that has their ``shape``) in
    ``dtype``: ``"kernel"`` (``kernels/mlp_rungs.py``, every rung of a
    block from one read of its ``[z1; zd1]``) or ``"xla"`` (``lax.map`` of
    a rung), read from the input whatever walk layer 1 takes: both hand
    the rungs float ``z1`` and ``zd1``."""
    return mlp_rungs.rung_path(layer_sizes, X.shape[2] * X.shape[3], dtype)


def dot_highest(spec: str, a, b):
    """``einsum(spec, a, b)`` of two float operands at matmul precision
    ``highest``: float32 grade on the MXU."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _softmax_loss(z, y, w):
    """Summed cross-entropy of logits ``z`` ``(k, R)`` at labels ``y``
    ``(R,)`` under weights ``w``, and the residual ``w (p - onehot)``
    (reference SoftmaxLayerWithCrossEntropyLoss)."""
    m = z.max(0)
    e = jnp.exp(z - m)
    den = e.sum(0)
    hit = y[None] == jnp.arange(z.shape[0], dtype=y.dtype)[:, None]
    zy = jnp.where(hit, z, 0.0).sum(0)
    loss = (w * (m + jnp.log(den) - zy)).sum()
    return loss, w[None] * (e / den[None] - hit.astype(z.dtype))


def _upper(layers, z1):
    """From layer 1's pre-activations ``z1`` ``(h1, R)``, the sigmoid
    inputs of every further layer and the logits."""
    hs, z = [], z1
    for W, b in layers[1:]:
        h = jax.nn.sigmoid(z)
        hs.append(h)
        z = dot_highest("io,ir->or", W, h) + b[:, None]
    return hs, z


class MlpObjFunc(OptimObjFunc):
    """Cross-entropy over softmax outputs (reference
    SoftmaxLayerWithCrossEntropyLoss + AnnObjFunc)."""

    qn_program = MLP_QN_PROGRAM

    def __init__(self, layer_sizes: Sequence[int], l2: float = 0.0):
        super().__init__(stack_sizes(layer_sizes), l1=0.0, l2=l2)
        self.layer_sizes = list(layer_sizes)

    def prepare_data(self, data, num_workers):
        """A ``DenseBlockColumn`` is packed as the linear objectives pack
        theirs (labels as whole numbers); host rows pass as they are."""
        if isinstance(data.get("X"), DenseBlockColumn):
            return pack_dense(data, num_workers, label_dtype=np.int32)
        return dict(data), {}

    def _loss_sum(self, coef, X, y, w):
        logits = mlp_forward(coef, X, self.layer_sizes)
        lse = jax.nn.logsumexp(logits, axis=1)
        picked = jnp.take_along_axis(logits, y[:, None].astype(jnp.int32), 1)[:, 0]
        return (w * (lse - picked)).sum()

    def calc_grad_shard(self, data, coef):
        X, y, w = data["X"], data["y"], data["w"]
        loss, grad = jax.value_and_grad(self._loss_sum)(coef, X, y, w)
        return grad, loss, w.sum()

    def line_losses_shard(self, data, coef, direction, steps, eta0=None):
        X, y, w = data["X"], data["y"], data["w"]

        def one(s):
            return self._loss_sum(coef - s * direction, X, y, w)

        return jax.vmap(one)(steps)

    # -- a blocked table, walked ------------------------------------------
    def _rows(self, data, i):
        """Block ``i``'s labels and weights a row each, and its rows."""
        w = block_at(data["w"], i)
        rows = w.shape[0] * w.shape[1]
        return block_at(data["y"], i).reshape(rows), w.reshape(rows), rows

    def _layer1(self, data, i, walk):
        """Layer 1's two products over block ``i`` on ``walk``:
        ``forward(A, b, cuts)``, ``A . x + b`` ``(m, rows)`` cut into
        ``cuts`` equal runs of rows, and ``backward(C)`` ``(m, d)`` =
        ``C . x^T``, ``C`` ``(m, rows)``."""
        X = data["X"]
        if walk == "kernel":
            def forward(A, b, cuts=1):
                return tuple(z.astype(A.dtype)
                             for z in layer1.forward(X, i, A, b, cuts))

            def backward(C):
                return layer1.backward(X, i, C).astype(C.dtype)
            return forward, backward
        xb = block_at(X, i)

        def forward(A, b, cuts=1):
            z = block_forward(xb, A).reshape(A.shape[0], -1) + b[:, None]
            h = A.shape[0] // cuts
            return tuple(z[k * h:(k + 1) * h] for k in range(cuts))

        def backward(C):
            return block_backward(xb, C.reshape((-1,) + xb.shape[1:]))
        return forward, backward

    def grad_pass(self, data, coef):
        """One walk of a blocked shard: ``(grad, loss, wsum, None, rows)``.
        A block's forward, loss and backward come from one read of the
        block; nothing is kept for the line search (layer 1's
        pre-activations over the whole table would not fit)."""
        layers = unstack(coef, self.layer_sizes)
        W1, b1 = layers[0]
        A, fb = fold_coef(data, W1.T)
        d = data["X"].shape[1]
        walk = walk_path(data["X"], self.layer_sizes)

        def block(i):
            y, w, rows = self._rows(data, i)
            forward, backward = self._layer1(data, i, walk)
            with jax.named_scope("mlp_forward"):
                z1, = forward(A, fb + b1)
                hs, z = _upper(layers, z1)
                loss, delta = _softmax_loss(z, y, w)
            with jax.named_scope("mlp_backward"):
                sums = []
                for (W, _), h in zip(reversed(layers[1:]), reversed(hs)):
                    sums = [dot_highest("ir,or->io", h, delta),
                            delta.sum(1)] + sums
                    delta = dot_highest("io,or->ir", W, delta) \
                        * (h * (1.0 - h))
                G1 = backward(delta)
            return ((G1, delta.sum(1), *sums, jnp.stack([loss, w.sum()])),
                    (w != 0).sum(dtype=jnp.int32), None)

        init = []
        for j, (W, b) in enumerate(layers):
            init += [jnp.zeros((W.shape[1], d) if j == 0 else W.shape,
                               W.dtype), jnp.zeros_like(b)]
        sums, rows, _ = _walk(data, block, tuple(init) + (
            jnp.zeros((2,), coef.dtype),))
        G1, csum1, tail = sums[0], sums[1], sums[-1]
        parts = [unfold_grad(data, G1, csum1, d).T.reshape(-1), csum1]
        parts += [s.reshape(-1) for s in sums[2:-1]]
        return jnp.concatenate(parts), tail[0], tail[1], None, rows

    def line_pass(self, data, coef, direction, steps, eta0=None):
        """The second walk of a superstep: ``(losses at coef - steps[j] *
        direction (num_steps,), rows)``. Layer 1 is linear in the step, so
        a block's ``[z1; zd1]`` is one product of the stacked ``[W1;
        D1]``; each rung then runs the sigmoid, the upper layers at
        ``coef - step * direction`` and the loss, all the rungs of a block
        in one kernel call on the ``kernel`` path (:func:`rung_path`), which
        takes its float product from :func:`dot_highest` as the line pass
        is traced."""
        layers = unstack(coef, self.layer_sizes)
        dirs = unstack(direction, self.layer_sizes)
        (W1, b1), (D1, db1) = layers[0], dirs[0]
        A, fb = fold_coef(data, jnp.concatenate([W1.T, D1.T]))
        bias = fb + jnp.concatenate([b1, db1])
        walk = walk_path(data["X"], self.layer_sizes)
        rungs = rung_path(data["X"], self.layer_sizes, coef.dtype)

        def block(i):
            y, w, rows = self._rows(data, i)
            forward, _ = self._layer1(data, i, walk)
            with jax.named_scope("mlp_line"):
                z1, zd1 = forward(A, bias, 2)
                if rungs == "kernel":
                    losses = mlp_rungs.losses(z1, zd1, y, w, layers[1:],
                                              dirs[1:], steps, dot_highest)
                    return (losses,), (w != 0).sum(dtype=jnp.int32), None

                def rung(s):
                    upper = layers[:1] + [
                        (W - s * D, b - s * db) for (W, b), (D, db)
                        in zip(layers[1:], dirs[1:])]
                    return _softmax_loss(_upper(upper, z1 - s * zd1)[1],
                                         y, w)[0]
                losses = jax.lax.map(rung, steps)
            return (losses,), (w != 0).sum(dtype=jnp.int32), None

        (losses,), rows, _ = _walk(
            data, block, (jnp.zeros(steps.shape, coef.dtype),))
        return losses, rows
