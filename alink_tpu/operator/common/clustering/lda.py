"""LDA core kernels — TPU-native online-variational + batched EM training.

Re-design of the reference LDA internals
(operator/common/clustering/lda/: OnlineCorpusStep.java,
UpdateLambdaAndAlpha.java, EmCorpusStep.java, EmLogLikelihood.java,
BuildOnlineLdaModel.java, BuildEmLdaModel.java; driven from
operator/batch/clustering/LdaTrainBatchOp.java:132-190).

TPU-first changes vs the reference:

* Corpus representation: padded ``(n_docs, max_len)`` token-id + count
  arrays (bag-of-words per doc, zero-count padding) instead of per-row
  ``SparseVector``s — static shapes for XLA, docs partition-resident on
  devices across supersteps.
* Online method = Hoffman-style stochastic variational inference. The
  per-minibatch E-step is a fixed-trip ``lax.fori_loop`` of *batched*
  digamma/softmax updates where the hot contractions
  (``expElogtheta @ expElogbeta[:, ids]``) are einsums on the MXU; the
  reference's per-document Java loops (OnlineCorpusStep.java) have no
  analogue. Sufficient stats are scatter-added with ``segment_sum`` and
  combined across workers with one ``psum`` (replacing
  ``AllReduce(wordTopicStat)``).
* EM method: the reference uses collapsed Gibbs sampling
  (EmCorpusStep.java) — a per-token sequential sampler that is hostile to
  a systolic array. We train the same model shape (the ``gamma``
  word-topic count matrix incl. a trailing topic-total row,
  LdaModelData.java ``gamma``) with batched variational EM: per-superstep
  document E-step (doc-topic responsibilities) + psum'd expected
  word-topic counts. Deterministic, matmul-shaped, same predict formulas.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ....engine import IterativeComQueue
from ..nlp.text import _tokens


def encode_corpus(texts, index: dict, max_len: Optional[int] = None,
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Texts -> padded (n, L) word-id and count arrays (bag of words).

    Out-of-vocabulary tokens are dropped (reference Document2Vector via
    DocCountVectorizerModelMapper). Tokenization is the shared ``_tokens``
    (the same one ``train_doc_count_vectorizer`` builds the vocab with).
    Padding has count 0 and id 0.
    """
    docs = []
    for t in texts:
        toks = _tokens(t)
        bag = {}
        for w in toks:
            i = index.get(w)
            if i is not None:
                bag[i] = bag.get(i, 0.0) + 1.0
        docs.append(sorted(bag.items()))
    L = max_len or max((len(d) for d in docs), default=1)
    L = max(L, 1)
    n = len(docs)
    ids = np.zeros((n, L), np.int32)
    cnts = np.zeros((n, L), np.float64)
    for r, d in enumerate(docs):
        for c, (i, v) in enumerate(d[:L]):
            ids[r, c] = i
            cnts[r, c] = v
    return ids, cnts


def _e_step(ids, cnts, expElogbeta, alpha, key, n_inner: int = 50):
    """Batched variational E-step for one doc block.

    Returns (gamma (n,k), sstats (k,V)) where sstats already includes the
    expElogbeta factor (Hoffman'10 eq. 5 trick).
    """
    n, L = ids.shape
    k, V = expElogbeta.shape
    # (n, L, k): exp(E[log beta_{k, w_{nl}}])
    eb = jnp.take(expElogbeta.T, ids, axis=0)
    gamma0 = jax.random.gamma(key, 100.0, (n, k)) * 0.01

    def body(_, gamma):
        elt = jax.scipy.special.digamma(gamma) - \
            jax.scipy.special.digamma(gamma.sum(1, keepdims=True))
        expElt = jnp.exp(elt)
        phinorm = jnp.einsum("nk,nlk->nl", expElt, eb) + 1e-100
        return alpha + expElt * jnp.einsum("nl,nlk->nk", cnts / phinorm, eb)

    gamma = jax.lax.fori_loop(0, n_inner, body, gamma0)
    elt = jax.scipy.special.digamma(gamma) - \
        jax.scipy.special.digamma(gamma.sum(1, keepdims=True))
    expElt = jnp.exp(elt)
    phinorm = jnp.einsum("nk,nlk->nl", expElt, eb) + 1e-100
    contrib = (cnts / phinorm)[:, :, None] * expElt[:, None, :]   # (n, L, k)
    sstats = jax.ops.segment_sum(contrib.reshape(n * L, k), ids.reshape(-1),
                                 num_segments=V)                   # (V, k)
    return gamma, sstats.T * expElogbeta


def _bound_score(ids, cnts, gamma, beta_norm):
    """Per-block corpus log-likelihood proxy: sum c * log(theta . beta_w)."""
    theta = gamma / jnp.maximum(gamma.sum(1, keepdims=True), 1e-100)
    bw = jnp.take(beta_norm.T, ids, axis=0)                        # (n, L, k)
    pw = jnp.einsum("nk,nlk->nl", theta, bw)
    return (cnts * jnp.log(jnp.maximum(pw, 1e-100))).sum()


def _expElogbeta(lam):
    el = jax.scipy.special.digamma(lam) - \
        jax.scipy.special.digamma(lam.sum(1, keepdims=True))
    return jnp.exp(el)


def online_lda_train(ids: np.ndarray, cnts: np.ndarray, k: int, V: int,
                     num_iter: int = 10, alpha: float = -1.0, beta: float = -1.0,
                     tau0: float = 1024.0, kappa: float = 0.51,
                     subsample: float = 0.05, optimize_alpha: bool = True,
                     seed: int = 0, env=None, n_inner: int = 50):
    """Distributed online variational LDA (reference OnlineCorpusStep +
    UpdateLambdaAndAlpha on IterativeComQueue, LdaTrainBatchOp.java:176-190).

    Each superstep every worker samples ``subsample`` of its resident doc
    shard, runs the batched E-step, and the psum'd sufficient stats drive
    one natural-gradient lambda update with rho_t = (tau0+t)^-kappa.
    Returns (lambda (k,V), alpha (k,), loglik, log_perplexity).
    """
    if alpha <= 0:
        alpha = 1.0 / k
    if beta <= 0:
        beta = 1.0 / k
    n_total = ids.shape[0]
    rng = np.random.RandomState(seed)
    lam0 = rng.gamma(100.0, 1.0 / 100.0, (k, V))
    total_words = float(cnts.sum())

    def stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("lambda", jnp.asarray(lam0))
            ctx.put_obj("alpha_vec", jnp.full((k,), alpha))
            ctx.put_obj("score", jnp.zeros(()))
        ids_b = ctx.get_obj("ids")
        cnt_b = ctx.get_obj("cnts")
        lam = ctx.get_obj("lambda")
        avec = ctx.get_obj("alpha_vec")
        step = ctx.step_no
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        key = jax.random.fold_in(key, ctx.task_id)
        ksel, kgam = jax.random.split(key)
        sel = jax.random.uniform(ksel, (ids_b.shape[0],)) < subsample
        cnt_mb = jnp.where(sel[:, None], cnt_b, 0.0)
        eEb = _expElogbeta(lam)
        gamma, sstats = _e_step(ids_b, cnt_mb, eEb, avec[None, :], kgam, n_inner)
        mb_words = ctx.all_reduce_sum(cnt_mb.sum())
        sstats = ctx.all_reduce_sum(sstats)
        # natural-gradient step, rescaled minibatch -> corpus
        rho = (tau0 + step) ** (-kappa)
        scale = total_words / jnp.maximum(mb_words, 1.0)
        lam_new = (1.0 - rho) * lam + rho * (beta + scale * sstats)
        ctx.put_obj("lambda", lam_new)
        # alpha update: Newton step on the Dirichlet MLE over minibatch gammas.
        # Mask out zero-count rows: comqueue zero-pads doc shards to a
        # multiple of the worker count, and padded (or genuinely empty)
        # docs carry no evidence — their gamma == alpha would bias the MLE
        # toward self-consistency with the current value.
        if optimize_alpha:
            valid = sel & (cnt_b.sum(1) > 0)
            n_sel = ctx.all_reduce_sum(valid.sum() * 1.0)
            elt = jax.scipy.special.digamma(gamma) - \
                jax.scipy.special.digamma(gamma.sum(1, keepdims=True))
            logphat_sum = ctx.all_reduce_sum((elt * valid[:, None]).sum(0))
            logphat = logphat_sum / jnp.maximum(n_sel, 1.0)
            grad = n_sel * (jax.scipy.special.digamma(avec.sum())
                            - jax.scipy.special.digamma(avec) + logphat)
            q = -n_sel * jax.scipy.special.polygamma(1, avec)
            z = n_sel * jax.scipy.special.polygamma(1, avec.sum())
            b = (grad / q).sum() / (1.0 / z + (1.0 / q).sum())
            # reject the step if any component would go non-positive OR the
            # minibatch was empty (n_sel=0 makes q=-0 -> b=NaN)
            danger = ((avec - rho * (grad - b) / q) <= 0).any() | (n_sel < 1)
            avec_new = jnp.where(danger, avec, avec - rho * (grad - b) / q)
            ctx.put_obj("alpha_vec", avec_new)
        # corpus bound: score the *fitted* minibatch docs and scale to the
        # corpus (the standard SVI estimate) — unselected docs' gamma is
        # just the prior, so scoring the full shard with it would be noise
        beta_norm = lam_new / jnp.maximum(lam_new.sum(1, keepdims=True), 1e-100)
        ctx.put_obj("score", ctx.all_reduce_sum(
            _bound_score(ids_b, cnt_mb, gamma, beta_norm)) * scale)

    q = (IterativeComQueue(env=env, max_iter=max(num_iter, 1), seed=seed)
         .init_with_partitioned_data("ids", ids)
         .init_with_partitioned_data("cnts", cnts)
         .add(stage)
         # total_words is a data-derived constant baked into the trace;
         # lam0 derives from (seed, k, V) and seed rides the engine key
         .set_program_key(("lda_online", k, V, float(alpha), float(beta),
                           float(tau0), float(kappa), float(subsample),
                           bool(optimize_alpha), int(n_inner), total_words)))
    res = q.exec()
    lam = res.get("lambda")
    avec = res.get("alpha_vec")
    score = float(res.get("score"))
    log_perp = -score / max(total_words, 1.0)
    return np.asarray(lam), np.asarray(avec), score, log_perp


def em_lda_train(ids: np.ndarray, cnts: np.ndarray, k: int, V: int,
                 num_iter: int = 10, alpha: float = -1.0, beta: float = -1.0,
                 seed: int = 0, env=None, n_inner: int = 20):
    """Distributed full-batch EM (stands in for the reference's collapsed
    Gibbs EmCorpusStep.java — see module docstring for why).

    Per superstep: batched doc E-step against the current word-topic
    counts, then psum of expected counts rebuilds the global ``gamma``
    matrix. Doc-topic state stays partition-resident in the carry (the
    analogue of the reference's per-task topic assignments cached in
    SessionSharedObjs). Returns (wordTopicCounts (V,k), topicCounts (k,),
    alpha, beta, loglik, log_perplexity).

    alpha/beta here are the *actual* Dirichlet priors (the reference's
    Gibbs path shifts its defaults by +1 for the collapsed predictive
    rule, LdaTrainBatchOp.java:118-124; variational EM needs no shift —
    the same values are reused untouched at predict time).
    """
    if alpha <= 0:
        alpha = 50.0 / k
    if beta <= 0:
        beta = 0.01
    rng = np.random.RandomState(seed)
    wt0 = rng.gamma(100.0, 1.0 / 100.0, (k, V))
    total_words = float(cnts.sum())

    def stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("wt", jnp.asarray(wt0))
            ctx.put_obj("score", jnp.zeros(()))
        ids_b = ctx.get_obj("ids")
        cnt_b = ctx.get_obj("cnts")
        wt = ctx.get_obj("wt")
        # point-estimate topics with beta smoothing — the same formula
        # LdaModelData.word_topic_probs applies at predict time
        beta_hat = (wt + beta) / (wt.sum(1, keepdims=True) + V * beta)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), ctx.task_id)
        gamma, _ = _e_step(ids_b, cnt_b, beta_hat, alpha, key, n_inner)
        # expected word-topic counts: phi ~ theta_k * beta_kw
        theta = gamma / jnp.maximum(gamma.sum(1, keepdims=True), 1e-100)
        eb = jnp.take(beta_hat.T, ids_b, axis=0)                  # (n, L, k)
        phi = theta[:, None, :] * eb
        phi = phi / jnp.maximum(phi.sum(-1, keepdims=True), 1e-100)
        contrib = cnt_b[:, :, None] * phi
        n, L = ids_b.shape
        wt_new = jax.ops.segment_sum(contrib.reshape(n * L, k),
                                     ids_b.reshape(-1), num_segments=V).T
        ctx.put_obj("wt", ctx.all_reduce_sum(wt_new))
        ctx.put_obj("score", ctx.all_reduce_sum(
            _bound_score(ids_b, cnt_b, gamma, beta_hat)))

    q = (IterativeComQueue(env=env, max_iter=max(num_iter, 1), seed=seed)
         .init_with_partitioned_data("ids", ids)
         .init_with_partitioned_data("cnts", cnts)
         .add(stage)
         .set_program_key(("lda_em", k, V, float(alpha), float(beta),
                           int(n_inner))))
    res = q.exec()
    wt = np.asarray(res.get("wt"))                                # (k, V)
    score = float(res.get("score"))
    log_perp = -score / max(total_words, 1.0)
    return wt.T, wt.sum(1), alpha, beta, score, log_perp


def expand_tokens(ids: np.ndarray, cnts: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Bag-of-words (ids, counts) -> per-OCCURRENCE token arrays.

    Collapsed Gibbs assigns a topic per token occurrence, not per bag
    entry; a count-c entry expands to c slots. Returns (tok (n, T) int32,
    mask (n, T) {0,1} f32) with zero padding, T = longest doc — never
    truncated, so counts are conserved exactly (the Gibbs invariant)."""
    n = ids.shape[0]
    docs = []
    for r in range(n):
        row = np.repeat(ids[r], cnts[r].astype(np.int64))
        docs.append(row)
    T = max(max((len(d) for d in docs), default=1), 1)
    tok = np.zeros((n, T), np.int32)
    mask = np.zeros((n, T), np.float32)
    for r, d in enumerate(docs):
        tok[r, :len(d)] = d
        mask[r, :len(d)] = 1.0
    return tok, mask


def gibbs_lda_train(ids: np.ndarray, cnts: np.ndarray, k: int, V: int,
                    num_iter: int = 50, alpha: float = -1.0,
                    beta: float = -1.0, seed: int = 0, env=None):
    """Distributed collapsed-Gibbs LDA — the TPU shape of the reference's
    EmCorpusStep (LdaTrainBatchOp.java:135; VERDICT r2 #7).

    The reference's sampler walks tokens sequentially, updating global
    counts token by token — hostile to a systolic array. The TPU-native
    equivalent is the standard distributed approximation (AD-LDA,
    Newman et al. JMLR'09) with Jacobi-style within-worker updates:

    * per-token topic assignments ``z`` live DEVICE-RESIDENT in the
      superstep carry, sharded with the doc partition (the analogue of
      the reference's per-task topic arrays in SessionSharedObjs);
    * each superstep rebuilds doc-topic counts ``nd`` (one-hot einsum),
      word-topic counts ``nw`` (scatter-add, ``lax.psum`` across
      workers — the reference's AllReduce of wordTopicStat), subtracts
      each token's OWN contribution, and samples every token in
      parallel with ``jax.random.categorical`` over the collapsed
      posterior (nd-z+alpha)*(nw-z+beta)/(nt-z+V*beta);
    * counts re-psum next superstep, so cross-worker staleness is one
      superstep — exactly AD-LDA's approximation.

    Default priors mirror the reference Gibbs path INCLUDING its +1
    shift (alpha=50/k+1, beta=0.01+1, LdaTrainBatchOp.java:118-124);
    explicitly-passed alpha/beta are used as given in the collapsed
    rule. Returns (wordTopicCounts
    (V, k), topicCounts (k,), alpha, beta, loglik, log_perplexity).
    """
    if alpha <= 0:
        alpha = 50.0 / k + 1.0
    if beta <= 0:
        beta = 0.01 + 1.0
    tok, mask = expand_tokens(ids, cnts)
    n, T = tok.shape
    total_words = float(mask.sum())
    rng = np.random.RandomState(seed)
    z0 = rng.randint(0, k, size=(n, T)).astype(np.int32)

    def stage(ctx):
        if ctx.is_init_step:
            ctx.put_obj("z", ctx.get_obj("z_init"))
        tok_b = ctx.get_obj("tok")
        mask_b = ctx.get_obj("mask")
        z = ctx.get_obj("z")
        oh = jax.nn.one_hot(z, k, dtype=jnp.float32) * mask_b[..., None]
        nd = oh.sum(1)                                         # (n, k)
        # word-topic counts: scatter over flat (topic, word) cells
        flat = (z.astype(jnp.int32) * V + tok_b).reshape(-1)
        nw = jnp.zeros((k * V,), jnp.float32).at[flat].add(
            mask_b.reshape(-1)).reshape(k, V)
        nw = ctx.all_reduce_sum(nw)                            # psum
        nt = nw.sum(1)                                         # (k,)
        # per-token posterior with own contribution removed (collapsed rule)
        nd_m = nd[:, None, :] - oh                             # (n, T, k)
        nw_tok = jnp.take(nw.T, tok_b, axis=0) - oh            # (n, T, k)
        nt_m = nt[None, None, :] - oh                          # (n, T, k)
        logp = (jnp.log(nd_m + alpha) + jnp.log(nw_tok + beta)
                - jnp.log(nt_m + V * beta))
        key = jax.random.fold_in(jax.random.PRNGKey(seed), ctx.step_no)
        key = jax.random.fold_in(key, ctx.task_id)
        z_new = jax.random.categorical(key, logp, axis=-1).astype(jnp.int32)
        z_new = jnp.where(mask_b > 0, z_new, 0)
        ctx.put_obj("z", z_new)

    q = (IterativeComQueue(env=env, max_iter=max(num_iter, 1), seed=seed)
         .init_with_partitioned_data("tok", tok)
         .init_with_partitioned_data("mask", mask)
         .init_with_partitioned_data("z_init", z0)
         .add(stage)
         .set_program_key(("lda_gibbs", k, V, float(alpha), float(beta))))
    res = q.exec()
    # final global counts from the final assignments (all shards)
    z_fin = res.concat("z", total=n)
    nw = np.zeros((k, V), np.float64)
    np.add.at(nw.reshape(-1), (z_fin.astype(np.int64) * V
                               + tok).reshape(-1)[mask.reshape(-1) > 0], 1.0)
    # score recomputed from the FINAL assignments so the reported
    # perplexity matches the returned counts (the in-carry score is one
    # superstep stale: it is computed from the counts before the last
    # resample)
    nd = np.zeros((n, k), np.float64)
    np.add.at(nd.reshape(-1), (np.arange(n)[:, None] * k
                               + z_fin).reshape(-1)[mask.reshape(-1) > 0], 1.0)
    theta = (nd + alpha) / (nd.sum(1, keepdims=True) + k * alpha)
    beta_hat = (nw + beta) / (nw.sum(1, keepdims=True) + V * beta)
    # chunk over docs: beta_hat.T[tok] for the whole corpus would be an
    # (n, T, k) float64 allocation
    score = 0.0
    for s0 in range(0, n, 2048):
        sl = slice(s0, min(s0 + 2048, n))
        pw = np.einsum("nk,ntk->nt", theta[sl], beta_hat.T[tok[sl]])
        score += float((mask[sl] * np.log(np.maximum(pw, 1e-100))).sum())
    log_perp = -score / max(total_words, 1.0)
    return nw.T, nw.sum(1), alpha, beta, score, log_perp


def lda_infer(ids: np.ndarray, cnts: np.ndarray, word_topic: np.ndarray,
              alpha, n_inner: int = 50, seed: int = 0) -> np.ndarray:
    """Doc-topic inference at predict time (reference LdaUtil /
    LdaModelMapper.predictResultDetail). word_topic: (V, k) p(w|z) columns
    (already normalized). Returns theta (n, k)."""
    from ....engine.comqueue import lazy_jit
    eEb = jnp.asarray(word_topic.T)                               # (k, V)
    alpha = jnp.asarray(alpha)
    key = jax.random.PRNGKey(seed)
    gamma, _ = lazy_jit(_e_step, static_argnums=(5,))(
        jnp.asarray(ids), jnp.asarray(cnts), eEb,
        alpha[None, :] if alpha.ndim == 1 else alpha, key, n_inner)
    gamma = np.asarray(gamma)
    return gamma / np.maximum(gamma.sum(1, keepdims=True), 1e-100)
