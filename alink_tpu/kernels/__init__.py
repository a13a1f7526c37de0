"""alink_tpu.kernels — the hand-written Pallas kernel tier (ISSUE 13).

The SURVEY's stated target stack is "JAX/XLA/pjit/pallas"; this package
hosts the hand-written kernels for the dispatch-floor holdouts plus the
ONE availability/demotion contract they all ride (``runtime``):

* ``runtime``  — availability (TPU or ``ALINK_TPU_PALLAS_INTERPRET=1``),
  one-time-warn demotion, eager shape-class probing;
* ``ftrl``     — the sparse FTRL state gather / duplicate-safe
  scatter-add kernels (VMEM-resident (z, n) slot tiles) and the
  chained-correction triangular matvec (``ALINK_TPU_FTRL_KERNEL``);
* ``serve``    — the fused encode-gather -> dot -> link serving score
  kernel (``ALINK_TPU_SERVE_FUSED``) and the opt-in bf16/int8
  low-precision score path (``ALINK_TPU_SERVE_DTYPE``);
* ``kmeans``   — the k-means|| candidate fold (ISSUE 30): distances,
  min, argmin and the ``(d2, nearest)`` update of a table block in one
  streamed pass; and Lloyd's superstep (ISSUE 32): distances, argmin and
  every cluster's centred sums, weight and inertia of a block in
  registers, Kahan-joined across the blocks inside the kernel. Neither
  has a flag: a fit takes them where its input allows
  (``kmeans.fold_path``, ``kmeans.lloyd_path``) and says so
  (``init_fold``, ``lloyd_pass``);
* ``linear``   — the multinomial quasi-Newton superstep's two passes over
  a blocked table of BYTES (ISSUE 38), one streamed kernel each
  (``qn_grad_pass``, ``qn_line_pass``): the block read once as 32-bit
  words with a sublane stride, widened to bfloat16 once, pass 1's two
  MXU products (logits, gradient sums) and softmax, loss and residual
  between them, pass 2's product and the whole ladder, fed from that
  copy; sums Kahan-joined in block order inside. No flag:
  ``linear.pass_path(dtype, d, S, m)`` reads ``kernel`` for one-byte
  integers (signed ones widened with their sign), ``S % 32 == 0``,
  ``m <= 40`` rows and ``2 <= d <= 8192`` where Pallas runs, else
  ``xla``; a fit says which in
  ``get_train_info()["paths"]["walk"]`` and counts
  ``alink_linear_pass_blocks_total{pass=, walk=}``. Parity with the XLA
  walk: ``2e-6`` of the largest logit / gradient entry, ``1e-6``
  relative on the sums, rows exact (``tests/test_linear_kernel.py``).

Every kernel is parity-pinned against its XLA path (bitwise where the
contract demands it, pinned tolerance where association differs) and
every flag-off path lowers byte-identically to pre-kernel-tier
programs — see tests/test_kernels.py and docs/performance.md
"Pallas kernel tier".
"""

from .runtime import (demote_once, eager_probe, interpret_mode,
                      pallas_available, pallas_interpret, reset_demotions)

__all__ = ["demote_once", "eager_probe", "interpret_mode",
           "pallas_available", "pallas_interpret", "reset_demotions"]
