"""Objective functions for linear-model training.

Re-design of the reference optimization objectives
(common/optim/objfunc/OptimObjFunc.java:60-80 ``calcGradient/updateGradient``;
common/linear/UnaryLossObjFunc.java; the 11 per-loss classes under
common/linear/unarylossfunc/ — LogLoss, Hinge, SmoothHinge, Square, Huber,
Exponential, Perceptron, Svr, ZeroOne).

TPU-first shape: objectives are pure jax functions over a **shard** of
training data held as device arrays — dense ``{"X"}`` or padded-COO sparse
``{"idx","val"}`` plus ``{"y","w"}`` — returning unnormalized sums
(grad, loss, weight). Cross-worker normalization happens after an
``AllReduce``, mirroring the reference's gradAllReduce/lossAllReduce stages.
Per-sample Java loops become one fused matmul/gather per shard (MXU).
Sample weights double as the padding mask (padded rows have w == 0).

A DENSE shard is blocked (``common/columnar.py``): ``X`` is the worker's
part of a ``DenseBlockColumn``, ``(blocks, d, S, 128)`` of float32 or of
one byte a value, ``y`` and ``w`` are ``(blocks, S, 128)``. Every dense
pass WALKS it block by block (``lax.fori_loop``, as ``clustering/kmeans.py``
and ``tree/hist.py`` walk theirs): a block's margins, loss, residual and
gradient sums are one read of the block, the block sums are joined with a
Kahan compensation, the rows are counted as whole numbers, and nothing of
shape ``(n, ...)`` exists but the kept margins. Standardization and the
intercept are FOLDED into the coefficients (``X_std . w = X . (w / std) -
(mean / std) . w``: the shard's ``scale`` and ``shift``), so the table is
never rewritten. ``OptimObjFunc.prepare_data`` packs host rows into that
form; there is no other dense form. The multinomial objective's two
passes over a table of BYTES are each one streamed Pallas kernel where
the input allows (``kernels/linear.py``; :func:`walk_path` reads which
from the shard, nothing sets it): the same arithmetic, the block read,
widened and laid out for the MXU once a pass.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....common.columnar import (as_block_column, block_values,
                                  block_weights)
from ....kernels import linear as pass_kernel
from ....kernels.linear import split3
from ..blocked import block_at, kahan_add


# ---------------------------------------------------------------------------
# unary losses: loss(eta, y) and d loss / d eta, with y in {-1, +1} for
# classification losses and real y for regression losses.
# ---------------------------------------------------------------------------

class UnaryLossFunc:
    name = "base"

    def loss(self, eta, y):  # pragma: no cover - interface
        raise NotImplementedError

    def derivative(self, eta, y):  # pragma: no cover - interface
        raise NotImplementedError

    def second_derivative(self, eta, y):
        raise NotImplementedError(f"{self.name} has no curvature (Newton unsupported)")


class LogLossFunc(UnaryLossFunc):
    """logistic loss (reference unarylossfunc/LogLossFunc.java)."""
    name = "log"

    def loss(self, eta, y):
        # log(1 + exp(-y*eta)), stable
        m = -y * eta
        return jnp.logaddexp(0.0, m)

    def derivative(self, eta, y):
        return -y * jax.nn.sigmoid(-y * eta)

    def second_derivative(self, eta, y):
        p = jax.nn.sigmoid(y * eta)
        return p * (1.0 - p)


class HingeLossFunc(UnaryLossFunc):
    name = "hinge"

    def loss(self, eta, y):
        return jnp.maximum(0.0, 1.0 - y * eta)

    def derivative(self, eta, y):
        return jnp.where(y * eta < 1.0, -y, 0.0)


class SmoothHingeLossFunc(UnaryLossFunc):
    """quadratically-smoothed hinge (reference SmoothHingeLossFunc.java)."""
    name = "smooth_hinge"

    def __init__(self, gamma: float = 1.0):
        self.gamma = gamma

    def loss(self, eta, y):
        z = y * eta
        g = self.gamma
        return jnp.where(z >= 1.0, 0.0,
                         jnp.where(z <= 1.0 - g, 1.0 - z - g / 2,
                                   (1.0 - z) ** 2 / (2 * g)))

    def derivative(self, eta, y):
        z = y * eta
        g = self.gamma
        return jnp.where(z >= 1.0, 0.0,
                         jnp.where(z <= 1.0 - g, -y, -y * (1.0 - z) / g))


class SquareLossFunc(UnaryLossFunc):
    name = "square"

    def loss(self, eta, y):
        return 0.5 * (eta - y) ** 2

    def derivative(self, eta, y):
        return eta - y

    def second_derivative(self, eta, y):
        return jnp.ones_like(eta)


class SvrLossFunc(UnaryLossFunc):
    """epsilon-insensitive (reference SvrLossFunc.java)."""
    name = "svr"

    def __init__(self, epsilon: float = 0.1):
        self.epsilon = epsilon

    def loss(self, eta, y):
        return jnp.maximum(0.0, jnp.abs(y - eta) - self.epsilon)

    def derivative(self, eta, y):
        r = eta - y
        return jnp.where(jnp.abs(r) <= self.epsilon, 0.0, jnp.sign(r))


class HuberLossFunc(UnaryLossFunc):
    name = "huber"

    def __init__(self, delta: float = 1.0):
        self.delta = delta

    def loss(self, eta, y):
        r = jnp.abs(eta - y)
        d = self.delta
        return jnp.where(r <= d, 0.5 * r ** 2, d * (r - 0.5 * d))

    def derivative(self, eta, y):
        r = eta - y
        d = self.delta
        return jnp.clip(r, -d, d)


class ExponentialLossFunc(UnaryLossFunc):
    name = "exponential"

    def loss(self, eta, y):
        return jnp.exp(-y * eta)

    def derivative(self, eta, y):
        return -y * jnp.exp(-y * eta)


class PerceptronLossFunc(UnaryLossFunc):
    name = "perceptron"

    def loss(self, eta, y):
        return jnp.maximum(0.0, -y * eta)

    def derivative(self, eta, y):
        return jnp.where(y * eta < 0.0, -y, 0.0)


class ZeroOneLossFunc(UnaryLossFunc):
    name = "zero_one"

    def loss(self, eta, y):
        return (jnp.sign(eta) != y).astype(eta.dtype)

    def derivative(self, eta, y):
        return jnp.zeros_like(eta)


LOSS_REGISTRY = {
    "log": LogLossFunc, "hinge": HingeLossFunc, "smooth_hinge": SmoothHingeLossFunc,
    "square": SquareLossFunc, "svr": SvrLossFunc, "huber": HuberLossFunc,
    "exponential": ExponentialLossFunc, "perceptron": PerceptronLossFunc,
    "zero_one": ZeroOneLossFunc,
}


# ---------------------------------------------------------------------------
# design-matrix ops over a data shard
# ---------------------------------------------------------------------------

def _fb_parts(data: Dict):
    """Precomputed one-hot factors, when the trainer's init superstep
    materialized them into the shard dict (fb_onehot_parts)."""
    if "fb_A" in data:
        return data["fb_A"], data["fb_B"]
    return None


def _join3(p, m: int):
    return (p[2 * m:] + p[m:2 * m]) + p[:m]


def _byte_table(xb) -> bool:
    return jnp.issubdtype(xb.dtype, jnp.integer)


def block_forward(xb, A):
    """``A . xb``: coefficient rows ``(m, d)`` against one feature-major
    block ``(d, S, 128)`` -> ``(m, S, 128)`` margins in ``A``'s dtype, at
    float32 grade whatever the matmul default is: a block of bytes goes
    to the MXU as exact bfloat16 against ``split3(A)``, a block of floats
    at precision ``highest``."""
    m = A.shape[0]
    d, S, L = xb.shape
    if _byte_table(xb):
        p = jnp.einsum("cd,dr->cr", split3(A),
                       xb.reshape(d, S * L).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return _join3(p, m).reshape(m, S, L).astype(A.dtype)
    return jnp.einsum("cd,dsl->csl", A, xb.astype(A.dtype),
                      precision=jax.lax.Precision.HIGHEST)


def block_backward(xb, C):
    """``C . xb^T``: per-row values ``(m, S, 128)`` against one block
    ``(d, S, 128)`` -> ``(m, d)`` sums over the block's rows, float32
    grade as :func:`block_forward`."""
    m = C.shape[0]
    d, S, L = xb.shape
    if _byte_table(xb):
        p = jnp.einsum("cr,dr->cd", split3(C.reshape(m, S * L)),
                       xb.reshape(d, S * L).astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return _join3(p, m).astype(C.dtype)
    return jnp.einsum("csl,dsl->cd", C, xb.astype(C.dtype),
                      precision=jax.lax.Precision.HIGHEST)


def fold_coef(data: Dict, Wm):
    """Coefficient rows ``Wm`` ``(m, dim)`` of the STANDARDIZED, intercept-
    first design as ``(A (m, d), b (m,))`` of the raw table: ``X_std .
    Wm^T = X . A^T + b``. ``dim == d + 1`` says the first coefficient is
    the intercept; ``data["scale"]`` (``1 / std``) and ``data["shift"]``
    (``mean / std``) say the design is standardized."""
    d = data["X"].shape[1]
    icpt = Wm.shape[1] == d + 1
    F = Wm[:, 1:] if icpt else Wm
    b = Wm[:, 0] if icpt else jnp.zeros((Wm.shape[0],), Wm.dtype)
    if "scale" in data:
        b = b - (F * data["shift"]).sum(1)
        F = F * data["scale"]
    return F, b


def unfold_grad(data: Dict, G, csum, dim: int):
    """The gradient rows ``(m, dim)`` in the standardized, intercept-first
    design from the raw sums of a pass: ``G`` ``(m, d)`` = ``sum c x`` and
    ``csum`` ``(m,)`` = ``sum c``."""
    d = data["X"].shape[1]
    if "scale" in data:
        G = G * data["scale"] - data["shift"] * csum[:, None]
    if dim == d + 1:
        G = jnp.concatenate([csum[:, None], G], 1)
    return G


def standardized_block(data: Dict, i, dim: int, dtype):
    """Block ``i`` of the standardized, intercept-first design written
    out, ``(dim, S, 128)``: for what is O(dim^2) anyway (Newton's
    Hessian). The gradient passes never write it."""
    xb = block_at(data["X"], i).astype(dtype)
    if "scale" in data:
        xb = xb * data["scale"][:, None, None] \
            - data["shift"][:, None, None]
    if dim == xb.shape[0] + 1:
        xb = jnp.concatenate([jnp.ones((1,) + xb.shape[1:], dtype), xb], 0)
    return xb


def _walk(data: Dict, block_fn, sums, keep=None):
    """Walk a dense shard block by block: ``block_fn(i)`` gives ``(block
    sums, rows seen (int32), kept)``; the sums (a tuple of arrays) are
    joined with a Kahan compensation, the rows added as whole numbers,
    ``kept`` written to ``keep[i]``. Returns ``(sums, rows, keep)``."""
    nbl = data["X"].shape[0]

    def body(i, c):
        acc, comp, rows, kept = c
        blk, r, z = block_fn(i)
        pairs = [kahan_add(a, k, x) for a, k, x in zip(acc, comp, blk)]
        if kept is not None:
            kept = jax.lax.dynamic_update_index_in_dim(kept, z, i, 0)
        return (tuple(p[0] for p in pairs), tuple(p[1] for p in pairs),
                rows + r, kept)

    acc, _, rows, keep = jax.lax.fori_loop(
        0, nbl, body, (tuple(sums), tuple(jnp.zeros_like(a) for a in sums),
                       jnp.asarray(0, jnp.int32), keep))
    return acc, rows, keep


def walk_path(X, m: int) -> str:
    """Which walk the multinomial passes take over a dense shard's table
    ``X`` (blocks ``(nbl, d, S, 128)``, or anything that has their
    ``dtype`` and ``shape``) with ``m`` coefficient rows: ``"kernel"``
    (``kernels/linear.py``) or ``"xla"`` (:func:`_walk`), read from the
    input (``pass_kernel.pass_path``)."""
    return pass_kernel.pass_path(X.dtype, X.shape[1], X.shape[2], m)


def matvec(data: Dict, coef, fb_meta=None):
    """margins = X @ coef for a padded-COO or field-blocked shard (a dense
    shard is walked: :func:`block_forward`).

    Field-blocked shards ({"fb_idx"}) route to the factored-one-hot MXU
    kernel (ops/fieldblock.py) instead of XLA's serialized random gather.
    """
    if "fb_idx" in data:
        if fb_meta is None:
            raise ValueError("shard has 'fb_idx' but no FieldBlockMeta was "
                             "provided (pass fb_meta= to the objective)")
        from ....ops.fieldblock import fb_matvec
        return fb_matvec(data["fb_idx"], coef, fb_meta, val=data.get("fb_val"),
                         parts=_fb_parts(data))
    return (data["val"] * coef[data["idx"]]).sum(-1)


def rmatvec(data: Dict, c, dim: int, fb_meta=None):
    """X^T @ c — gradient accumulation of a sparse shard.

    Field-blocked: scatter-free factored one-hot (ops/fieldblock.py).
    Padded-COO: XLA scatter-add (slow on TPU — the general-sparsity
    fallback)."""
    if "fb_idx" in data:
        if fb_meta is None:
            raise ValueError("shard has 'fb_idx' but no FieldBlockMeta was "
                             "provided (pass fb_meta= to the objective)")
        from ....ops.fieldblock import fb_rmatvec
        return fb_rmatvec(data["fb_idx"], c, fb_meta, val=data.get("fb_val"),
                          parts=_fb_parts(data))
    contrib = data["val"] * c[:, None]
    return jnp.zeros(dim, contrib.dtype).at[data["idx"].reshape(-1)].add(
        contrib.reshape(-1))


def densify_shard(data: Dict, dim: int, fb_meta=None):
    """(n, dim) dense design matrix from a SPARSE shard layout.

    Only for algorithms whose memory is already O(dim^2) — Newton's Hessian
    (reference common/optim/Newton.java runs on any vector input because its
    Hessian is a dense dim x dim matrix regardless) — where the O(n*dim)
    scatter-densify is not the dominant cost. Hot gradient paths must keep
    using matvec/rmatvec, which never densify.
    """
    if "fb_idx" in data:
        if fb_meta is None:
            raise ValueError("shard has 'fb_idx' but no FieldBlockMeta was "
                             "provided (pass fb_meta= to the objective)")
        offs = jnp.arange(fb_meta.num_fields, dtype=data["fb_idx"].dtype) \
            * fb_meta.field_size
        idx = data["fb_idx"] + offs[None, :]
        val = data.get("fb_val")
        if val is None:
            val = jnp.ones(idx.shape, jnp.float32)
    else:
        idx, val = data["idx"], data["val"]
    n = idx.shape[0]
    # padding entries carry val == 0, so scatter-add at their (0-)index is a no-op
    return jnp.zeros((n, dim), val.dtype).at[
        jnp.arange(n)[:, None], idx].add(val)


def pack_dense(data: Dict, num_workers: int, label_dtype=None
               ) -> Tuple[Dict, Dict]:
    """A linear objective's dense inputs in the one form its passes walk:
    ``X`` (host rows ``(n, d)`` or a ``DenseBlockColumn``, used where it
    lies) as blocks, ``y`` and ``w`` laid out beside it (``w`` missing or
    ``None``: unit weights, made where the table lives). Returns
    ``(partitioned, broadcast)``: the blocks, and ``scale`` / ``shift``
    where the caller gave them. A sparse input passes through."""
    if "X" not in data:
        return dict(data), {}
    extra = set(data) - {"X", "y", "w", "scale", "shift"}
    if extra:
        raise ValueError(f"dense linear inputs {sorted(extra)} have no "
                         f"blocked form")
    col = as_block_column(data["X"], num_workers)
    w = data.get("w")
    dt = float_dtype(w, data.get("scale"), data["y"])
    y = block_values(col, data["y"])
    want = np.dtype(label_dtype or dt)
    parts = {"X": col.blocks,
             "y": y if y.dtype == want else y.astype(want),
             "w": block_weights(col, getattr(w, "blocks", w), dt)}
    consts = {k: np.asarray(data[k], dt) for k in ("scale", "shift")
              if k in data}
    return parts, consts


def blocked(data: Dict) -> bool:
    """Whether ``data`` is a dense shard in the blocked form a linear
    objective walks (another objective's ``X`` is its own rows)."""
    return getattr(data.get("X"), "ndim", 0) == 4


def float_dtype(*values) -> np.dtype:
    """The float dtype a fit computes in: that of the first of ``values``
    (its weights, else its fold constants, else its labels) that is
    float32 or float64, else float32."""
    for v in values:
        if v is None:
            continue
        dt = np.dtype(getattr(v, "dtype", None) or np.asarray(v).dtype)
        if dt in (np.float32, np.float64):
            return dt
    return np.dtype(np.float32)


class OptimObjFunc:
    """Base objective: per-shard grad/loss/hessian + global regularization."""

    def __init__(self, dim: int, l1: float = 0.0, l2: float = 0.0,
                 reg_free_head: int = 0):
        self.dim = int(dim)
        self.l1 = float(l1)
        self.l2 = float(l2)
        # first `reg_free_head` coefficients (the intercept) are unregularized
        self.reg_free_head = int(reg_free_head)

    def _reg_mask(self, coef):
        if self.reg_free_head == 0:
            return jnp.ones_like(coef)
        return jnp.concatenate([jnp.zeros(self.reg_free_head, coef.dtype),
                                jnp.ones(self.dim - self.reg_free_head, coef.dtype)])

    def regular_loss(self, coef, l1=None, l2=None):
        """The penalty at ``coef``; ``l1`` / ``l2`` given as traced values
        (the quasi-Newton programs take them as DATA, so a sweep over
        them compiles once) or, left out, the objective's own."""
        l1 = self.l1 if l1 is None else l1
        l2 = self.l2 if l2 is None else l2
        m = self._reg_mask(coef)
        sq, ab = ((coef * m) ** 2).sum(), jnp.abs(coef * m).sum()
        # ``zero`` is a 0 the compiler cannot see (``ab`` is never
        # negative). Adding it to each scaled sum makes the penalty's last
        # addition one of two SUMS: a compiler that contracts ``a * b + c``
        # into one rounding can then only contract ``a * b + 0``, which
        # rounds as ``a * b`` does. Without it the serial program (whose
        # scalar tail fuses into one kernel) contracted one product into
        # the sum and the sweep's lane (whose tail does not) did neither,
        # and the two are pinned bitwise (tests/test_sweep.py)
        zero = jnp.minimum(ab, 0.0)
        return (0.5 * l2 * sq + zero) + (l1 * ab + zero)

    def l2_grad(self, coef, l2=None):
        l2 = self.l2 if l2 is None else l2
        return l2 * coef * self._reg_mask(coef)

    def structure(self) -> "OptimObjFunc":
        """This objective with its penalties zeroed: what a program that
        takes ``l1`` / ``l2`` as data closes over and is keyed by."""
        import copy
        out = copy.copy(self)
        out.l1 = out.l2 = 0.0
        return out

    def prepare_data(self, data: Dict, num_workers: int
                     ) -> Tuple[Dict, Dict]:
        """``(partitioned, broadcast)`` inputs of a fit from the caller's
        ``data``. The linear objectives pack a dense ``X`` into blocks
        (:func:`pack_dense`); any other objective takes its rows as they
        are."""
        return dict(data), {}

    # interface ----------------------------------------------------------
    def calc_grad_shard(self, data, coef):
        """-> (grad_sum, loss_sum, weight_sum) — unnormalized shard sums."""
        raise NotImplementedError

    def calc_grad_eta_shard(self, data, coef):
        """-> (grad, loss, wsum, eta); eta (per-shard margins at coef) may be
        passed back to line_losses_shard to skip recomputing the matvec."""
        grad, loss, wsum = self.calc_grad_shard(data, coef)
        return grad, loss, wsum, None

    def line_losses_shard(self, data, coef, direction, steps, eta0=None):
        """losses at coef - steps[j]*direction -> (num_steps,) shard sums."""
        raise NotImplementedError

    def hessian_shard(self, data, coef):
        raise NotImplementedError


class UnaryLossObjFunc(OptimObjFunc):
    """sum_i w_i * loss(x_i . coef, y_i) (reference common/linear/UnaryLossObjFunc.java).

    ``fb_meta`` (ops.fieldblock.FieldBlockMeta) enables the field-blocked
    fast path when the shard carries ``fb_idx``.
    """

    def __init__(self, unary_loss: UnaryLossFunc, dim: int, l1=0.0, l2=0.0,
                 reg_free_head: int = 0, fb_meta=None):
        super().__init__(dim, l1, l2, reg_free_head)
        self.unary_loss = unary_loss
        if fb_meta is not None and fb_meta.dim != self.dim:
            raise ValueError(f"fb_meta.dim {fb_meta.dim} != objective dim "
                             f"{self.dim} (dim must be num_fields*field_size)")
        self.fb_meta = fb_meta

    def prepare_data(self, data, num_workers):
        return pack_dense(data, num_workers)

    def calc_grad_shard(self, data, coef):
        grad, loss, wsum, _ = self.calc_grad_eta_shard(data, coef)
        return grad, loss, wsum

    def calc_grad_eta_shard(self, data, coef):
        """(grad, loss, wsum, eta) — eta is reusable by the same-superstep
        line search (margins at the unmoved coef), saving one matvec pass."""
        if "X" in data:
            return self.grad_pass(data, coef)[:4]
        eta = matvec(data, coef, self.fb_meta)
        y, w = data["y"], data["w"]
        loss = (w * self.unary_loss.loss(eta, y)).sum()
        c = w * self.unary_loss.derivative(eta, y)
        grad = rmatvec(data, c, self.dim, self.fb_meta)
        return grad, loss, w.sum(), eta

    def grad_pass(self, data, coef):
        """One walk of a dense shard: ``(grad, loss, wsum, eta, rows)``,
        a block's margins, loss, residual and gradient sums from ONE read
        of the block; ``eta`` ``(blocks, S, 128)`` is kept for the line
        search, ``rows`` are the rows of non-zero weight, counted."""
        dt = coef.dtype
        A, b = fold_coef(data, coef[None])
        d = data["X"].shape[1]

        def block(i):
            xb = block_at(data["X"], i)
            y, w = block_at(data["y"], i), block_at(data["w"], i)
            with jax.named_scope("qn_logits"):
                eta = block_forward(xb, A)[0] + b[0]
                loss = (w * self.unary_loss.loss(eta, y)).sum()
                c = w * self.unary_loss.derivative(eta, y)
            with jax.named_scope("qn_grad"):
                G = block_backward(xb, c[None])
            return ((G, jnp.stack([c.sum(), loss, w.sum()])),
                    (w != 0).sum(dtype=jnp.int32), eta)

        (G, tail), rows, eta = _walk(
            data, block, (jnp.zeros((1, d), dt), jnp.zeros((3,), dt)),
            keep=jnp.zeros(data["w"].shape, dt))
        grad = unfold_grad(data, G, tail[:1], self.dim)[0]
        return grad, tail[1], tail[2], eta, rows

    def line_losses_shard(self, data, coef, direction, steps, eta0=None):
        if "X" in data:
            return self.line_pass(data, coef, direction, steps, eta0)[0]
        if eta0 is None:
            eta0 = matvec(data, coef, self.fb_meta)
        etad = matvec(data, direction, self.fb_meta)
        y, w = data["y"], data["w"]

        def one(s):
            return (w * self.unary_loss.loss(eta0 - s * etad, y)).sum()

        return jax.vmap(one)(steps)

    def line_pass(self, data, coef, direction, steps, eta0=None):
        """The second walk of a superstep: ``(losses at coef - steps[j] *
        direction (num_steps,), rows)`` from one read of the table for
        the direction's margins and the margins ``eta0`` the gradient
        pass kept (made again here only where none were kept)."""
        dt = coef.dtype
        A, b = fold_coef(data, jnp.stack([direction, coef]))

        def block(i):
            xb = block_at(data["X"], i)
            y, w = block_at(data["y"], i), block_at(data["w"], i)
            with jax.named_scope("qn_line"):
                if eta0 is None:
                    z = block_forward(xb, A) + b[:, None, None]
                    etad, e0 = z[0], z[1]
                else:
                    etad = block_forward(xb, A[:1])[0] + b[0]
                    e0 = block_at(eta0, i)
                losses = jax.vmap(lambda s: (w * self.unary_loss.loss(
                    e0 - s * etad, y)).sum())(steps)
            return (losses,), (w != 0).sum(dtype=jnp.int32), None

        (losses,), rows, _ = _walk(
            data, block, (jnp.zeros(steps.shape, dt),))
        return losses, rows

    def hessian_shard(self, data, coef):
        if "X" in data:
            grad, loss, wsum, eta, _ = self.grad_pass(data, coef)

            def block(i):
                zb = standardized_block(data, i, self.dim, coef.dtype)
                h = block_at(data["w"], i) * self.unary_loss \
                    .second_derivative(block_at(eta, i),
                                       block_at(data["y"], i))
                return (jnp.einsum(
                    "asl,bsl->ab", zb * h[None], zb,
                    precision=jax.lax.Precision.HIGHEST),), 0, None

            (H,), _, _ = _walk(data, block, (jnp.zeros(
                (self.dim, self.dim), coef.dtype),))
            return H, grad, loss, wsum
        grad, loss, wsum, eta = self.calc_grad_eta_shard(data, coef)
        y, w = data["y"], data["w"]
        h = w * self.unary_loss.second_derivative(eta, y)
        Xd = densify_shard(data, self.dim, self.fb_meta)
        H = (Xd * h[:, None]).T @ Xd
        return H, grad, loss, wsum


class SoftmaxObjFunc(OptimObjFunc):
    """Multinomial logistic objective (reference common/linear/SoftmaxObjFunc.java).

    coef is the flattened (k-1, d) matrix — class k-1 is the pivot with zero
    logits, matching the reference's k-1 parameterization. ``data["y"]``
    holds integer class indices.
    """

    def __init__(self, k: int, d: int, l1=0.0, l2=0.0, reg_free_cols: int = 0):
        super().__init__((k - 1) * d, l1, l2, reg_free_head=0)
        self.k = int(k)
        self.d = int(d)
        self.reg_free_cols = reg_free_cols  # leading feature columns w/o reg (intercept)

    def _reg_mask(self, coef):
        m = jnp.ones((self.k - 1, self.d), coef.dtype)
        if self.reg_free_cols:
            m = m.at[:, :self.reg_free_cols].set(0.0)
        return m.reshape(-1)

    def prepare_data(self, data, num_workers):
        return pack_dense(data, num_workers, label_dtype=np.int32)

    def _logits(self, data, W):
        gathered = W.T[data["idx"]]           # (n, nnz, k-1)
        z = (gathered * data["val"][..., None]).sum(1)
        return jnp.concatenate([z, jnp.zeros((z.shape[0], 1), z.dtype)], axis=1)

    def _grad_loss_from_logits(self, data, logits):
        """(grad, loss, wsum, softmax probs) of a SPARSE shard at
        already-computed logits — shared by the gradient and Newton paths
        so each Newton step runs the design-matrix product once."""
        y, w = data["y"].astype(jnp.int32), data["w"]
        lse = jax.nn.logsumexp(logits, axis=1)
        loss = (w * (lse - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])).sum()
        p = jax.nn.softmax(logits, axis=1)
        delta = (p - jax.nn.one_hot(y, self.k, dtype=p.dtype)) * w[:, None]  # (n,k)
        delta = delta[:, :self.k - 1]  # drop pivot class
        contrib = delta[:, None, :] * data["val"][:, :, None]  # (n, nnz, k-1)
        flat_idx = data["idx"].reshape(-1)
        g = jnp.zeros((self.d, self.k - 1), contrib.dtype)
        g = g.at[flat_idx].add(contrib.reshape(-1, self.k - 1))
        grad = g.T.reshape(-1)
        return grad, loss, w.sum(), p

    # -- a dense shard, walked ------------------------------------------
    def _block_loss(self, z, y, w):
        """One block's summed loss at margins ``z`` ``(k - 1, S, 128)``
        (the pivot class's are 0), its ``logsumexp`` parts for the
        residual: ``(loss, exp(z - m), exp(-m), their sum)``."""
        m = jnp.maximum(z.max(0), 0.0)
        e, e0 = jnp.exp(z - m), jnp.exp(-m)
        den = e.sum(0) + e0
        hit = y[None] == jnp.arange(self.k - 1, dtype=y.dtype)[:, None, None]
        zy = jnp.where(hit, z, 0.0).sum(0)
        return (w * (m + jnp.log(den) - zy)).sum(), e, den, hit

    def grad_pass(self, data, coef):
        """One walk of a dense shard: ``(grad, loss, wsum, logits, rows)``.
        A block's logits, loss, residual and the gradient's block sum
        come from ONE read of the block; the logits ``(blocks, k - 1, S,
        128)`` are kept for the line search."""
        dt = coef.dtype
        km1 = self.k - 1
        A, b = fold_coef(data, coef.reshape(km1, self.d))
        d = data["X"].shape[1]
        if walk_path(data["X"], km1) == "kernel":
            G, tail, rows, logits = pass_kernel.grad_pass(
                data["X"], data["y"], data["w"], A, b)
            tail = tail.astype(dt)
            grad = unfold_grad(data, G.astype(dt), tail[:km1], self.d)
            return (grad.reshape(-1), tail[km1], tail[km1 + 1],
                    logits.astype(dt), rows)

        def block(i):
            xb = block_at(data["X"], i)
            y, w = block_at(data["y"], i), block_at(data["w"], i)
            with jax.named_scope("qn_logits"):
                z = block_forward(xb, A) + b[:, None, None]
                loss, e, den, hit = self._block_loss(z, y, w)
                delta = w[None] * (e / den[None] - hit.astype(dt))
            with jax.named_scope("qn_grad"):
                G = block_backward(xb, delta)
            tail = jnp.concatenate([delta.sum((1, 2)),
                                    jnp.stack([loss, w.sum()])])
            return (G, tail), (w != 0).sum(dtype=jnp.int32), z

        (G, tail), rows, logits = _walk(
            data, block, (jnp.zeros((km1, d), dt), jnp.zeros((km1 + 2,), dt)),
            keep=jnp.zeros((data["w"].shape[0], km1) + data["w"].shape[1:],
                           dt))
        grad = unfold_grad(data, G, tail[:km1], self.d).reshape(-1)
        return grad, tail[km1], tail[km1 + 1], logits, rows

    def line_pass(self, data, coef, direction, steps, eta0=None):
        """The second walk of a superstep: ``(losses at coef - steps[j] *
        direction (num_steps,), rows)``: one read of the table for the
        direction's logits, the ladder evaluated from them and the
        logits ``eta0`` the gradient pass kept."""
        dt = coef.dtype
        km1 = self.k - 1
        A, b = fold_coef(data, jnp.concatenate(
            [direction.reshape(km1, self.d), coef.reshape(km1, self.d)]))
        if eta0 is not None and walk_path(data["X"], km1) == "kernel":
            losses, rows = pass_kernel.line_pass(
                data["X"], data["y"], data["w"], eta0, A[:km1], b[:km1],
                steps)
            return losses.astype(dt), rows

        def block(i):
            xb = block_at(data["X"], i)
            y, w = block_at(data["y"], i), block_at(data["w"], i)
            with jax.named_scope("qn_line"):
                if eta0 is None:
                    z = block_forward(xb, A) + b[:, None, None]
                    zd, z0 = z[:km1], z[km1:]
                else:
                    zd = block_forward(xb, A[:km1]) + b[:km1, None, None]
                    z0 = block_at(eta0, i)
                losses = jnp.stack([
                    self._block_loss(z0 - steps[j] * zd, y, w)[0]
                    for j in range(steps.shape[0])])
            return (losses,), (w != 0).sum(dtype=jnp.int32), None

        (losses,), rows, _ = _walk(
            data, block, (jnp.zeros(steps.shape, dt),))
        return losses, rows

    def calc_grad_eta_shard(self, data, coef):
        if "X" in data:
            return self.grad_pass(data, coef)[:4]
        return self.calc_grad_shard(data, coef) + (None,)

    def calc_grad_shard(self, data, coef):
        if "X" in data:
            return self.grad_pass(data, coef)[:3]
        W = coef.reshape(self.k - 1, self.d)
        grad, loss, wsum, _ = self._grad_loss_from_logits(
            data, self._logits(data, W))
        return grad, loss, wsum

    def line_losses_shard(self, data, coef, direction, steps, eta0=None):
        if "X" in data:
            return self.line_pass(data, coef, direction, steps, eta0)[0]
        W = coef.reshape(self.k - 1, self.d)
        D = direction.reshape(self.k - 1, self.d)
        y, w = data["y"].astype(jnp.int32), data["w"]
        z0 = self._logits(data, W)
        zd = self._logits(data, D)

        def one(s):
            z = z0 - s * zd
            lse = jax.nn.logsumexp(z, axis=1)
            return (w * (lse - jnp.take_along_axis(z, y[:, None], 1)[:, 0])).sum()

        return jax.vmap(one)(steps)

    def hessian_shard(self, data, coef):
        """Full (k-1)d x (k-1)d Hessian (reference SoftmaxObjFunc.java
        calcHessian): block (a,b) is sum_i w_i (p_ia [a==b] - p_ia p_ib)
        x_i x_i^T, laid out to match the flattened (k-1, d) coef.

        Blocks are contracted one (a,b) pair at a time under lax.map so
        peak memory stays O(n*d) — a single three-operand einsum would
        materialize an O(n*d^2) or O(n*(k-1)^2*d) intermediate. A dense
        shard is walked row block by row block, its standardized block
        written out a block at a time."""
        km1 = self.k - 1
        pairs = jnp.stack(jnp.meshgrid(jnp.arange(km1), jnp.arange(km1),
                                       indexing="ij"), -1).reshape(-1, 2)

        def laid_out(blocks):
            return (blocks.reshape(km1, km1, self.d, self.d)
                    .transpose(0, 2, 1, 3).reshape(self.dim, self.dim))

        if "X" in data:
            grad, loss, wsum, logits, _ = self.grad_pass(data, coef)

            def row_block(i):
                zb = standardized_block(data, i, self.d, coef.dtype)
                w = block_at(data["w"], i)
                p = jax.nn.softmax(jnp.concatenate(
                    [block_at(logits, i), jnp.zeros_like(w)[None]], 0), 0)

                def pair(ab):
                    a, b = ab[0], ab[1]
                    same = (a == b).astype(p.dtype)
                    sw = w * (p[a] * same - p[a] * p[b])
                    return jnp.einsum("asl,bsl->ab", zb * sw[None], zb,
                                      precision=jax.lax.Precision.HIGHEST)
                return (jax.lax.map(pair, pairs),), 0, None

            (blocks,), _, _ = _walk(data, row_block, (jnp.zeros(
                (km1 * km1, self.d, self.d), coef.dtype),))
            return laid_out(blocks), grad, loss, wsum
        W = coef.reshape(self.k - 1, self.d)
        logits = self._logits(data, W)
        grad, loss, wsum, p_full = self._grad_loss_from_logits(data, logits)
        w = data["w"]
        p = p_full[:, :self.k - 1]
        Xd = densify_shard(data, self.d)

        def block(pair):
            a, b = pair[0], pair[1]
            same = (a == b).astype(p.dtype)
            s = w * (p[:, a] * same - p[:, a] * p[:, b])
            return Xd.T @ (s[:, None] * Xd)

        return laid_out(jax.lax.map(block, pairs)), grad, loss, wsum
