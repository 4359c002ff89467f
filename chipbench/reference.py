"""The comparisons that decide ``correct``, against the plain references in
``families/``: float32 at ``HIGHEST`` matmul precision, one row at a time
and one layer at a time (each layer recomputed in the backward pass), so
that they fit the chip beside nothing else.

Training: three steps of loss, gradient (clipped by the global norm, as the
optimizer gets it) and AdamW, from the seed's weights on the batches the
program trained on. As the configuration states, each forward pass reads
the weights stored in the configuration's dtype (bfloat16), rounded from
float32 master weights; all arithmetic is float32. Three numbers:
  * ``loss_rel_gap``: the largest |program - reference| / reference loss
    over the three steps;
  * ``grad_leaf_gap``: over leaves, the largest gap between the program's
    and the reference's norm of the first gradient, over the larger of
    the reference leaf's norm and the median leaf's;
  * ``update_leaf_gap``: the same for the change of the float32 master
    weights over the three steps, leaving out leaves whose reference
    gradient is under a thousandth of the median leaf's (Adam moves those
    by round-off alone).

Serving: the widest gap by which a served token's reference logit lies
below the reference's best logit at its position.
"""
from __future__ import annotations

import numpy as np

from .families import common as c
from .families import family
from .workload import PAD

_NO_DECAY = ("norm", "A_log", "D", "dt_bias", "beta_attn", "beta_ssm")
TINY_GRAD = 1e-3


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}" if prefix else k)
    else:
        yield prefix, tree


def _norms(tree) -> dict:
    import jax.numpy as jnp
    return {p: jnp.sqrt(jnp.sum(jnp.square(x))) for p, x in _paths(tree)}


def _decays(path: str) -> bool:
    last = path.split("/")[-1]
    return not ("norm" in last or last in _NO_DECAY)


def lr_at(opt, step: int) -> float:
    """Linear warm-up to ``peak_lr``, then cosine to ``min_lr_ratio``."""
    warm = min(1.0, (step + 1) / max(1, opt.warmup_steps))
    prog = min(max((step - opt.warmup_steps)
                   / max(1, opt.total_steps - opt.warmup_steps), 0.0), 1.0)
    cos = opt.min_lr_ratio + (1 - opt.min_lr_ratio) * 0.5 * (1 + np.cos(np.pi * prog))
    return opt.peak_lr * warm * cos


def loss_and_grad_fn(cfg, nm: c.Numerics, rows: int | None = None):
    """jit'd (float32 weights, batch (B, S+1)) -> (mean loss, mean grads):
    next-token cross-entropy over labels that are not padding, summed one
    row at a time. ``rows`` keeps only the first rows (the half-batch
    fault)."""
    import jax
    import jax.numpy as jnp
    fam = family(cfg.family)

    def row_loss(w, row):
        logits = fam.forward(w, row[:-1], cfg, nm)
        lab = row[1:]
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, lab[:, None], -1)[:, 0])
        mask = (lab != PAD).astype(jnp.float32)
        return jnp.sum(nll * mask), jnp.sum(mask)

    def fn(w, batch):
        if rows is not None:
            batch = batch[:rows]

        def body(acc, row):
            (s, n), g = jax.value_and_grad(row_loss, has_aux=True)(w, row)
            return jax.tree.map(jnp.add, acc, (s, n, g)), None

        zero = (jnp.zeros(()), jnp.zeros(()),
                jax.tree.map(jnp.zeros_like, w))
        (s, n, g), _ = jax.lax.scan(body, zero, batch)
        return s / n, jax.tree.map(lambda x: x / n, g)

    return jax.jit(fn)


def adamw_fn(opt, donate: bool = True):
    """jit'd AdamW step with clipping by the global norm and decoupled
    weight decay (not on norms, A_log, D, dt_bias, beta_*)."""
    import jax
    import jax.numpy as jnp

    def fn(master, m, v, grads, step, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt.clip_norm / jnp.maximum(gn, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        count = step + 1
        c1, c2 = 1 - opt.beta1 ** count, 1 - opt.beta2 ** count
        paths = dict(_paths(grads))
        flat = {}
        for p, g in paths.items():
            mm = opt.beta1 * _get(m, p) + (1 - opt.beta1) * g
            vv = opt.beta2 * _get(v, p) + (1 - opt.beta2) * g * g
            upd = (mm / c1) / (jnp.sqrt(vv / c2) + opt.eps)
            w = _get(master, p)
            wd = opt.weight_decay if _decays(p) else 0.0
            flat[p] = (w - lr * (upd + wd * w), mm, vv)
        tree = lambda i: _unflatten({p: t[i] for p, t in flat.items()})
        return tree(0), tree(1), tree(2), grads

    return jax.jit(fn, donate_argnums=(0, 1, 2) if donate else ())


def _get(tree, path):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _unflatten(flat):
    out = {}
    for p, v in flat.items():
        node = out
        *head, last = p.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def stored_fn(cfg, nm: c.Numerics):
    """jit'd weights -> the weights as a run stores them (``common.store``)."""
    import jax
    return jax.jit(lambda w: c.store(w, nm, cfg.dtype))


def initial(cfg, seed: int):
    """The seed's weights, as the program got them, widened to float32."""
    import jax
    from repro.models import Model
    from . import weights
    return jax.jit(c.load)(weights.make(Model(cfg).abstract_params(), seed))


def train_steps(cfg, seed: int, batches, opt, nm: c.Numerics = c.REFERENCE,
                rows: int | None = None, frozen: bool = False) -> dict:
    """Losses of ``len(batches)`` steps, norms of the first clipped gradient
    and of the master weights' change after the last step. ``rows`` and
    ``frozen`` plant the faults of half a batch and of a step that leaves
    its state unchanged."""
    import jax
    import jax.numpy as jnp
    master = initial(cfg, seed)
    m = jax.tree.map(jnp.zeros_like, master)
    v = jax.tree.map(jnp.zeros_like, master)
    lg = loss_and_grad_fn(cfg, nm, rows)
    step_fn = adamw_fn(opt, donate=not frozen)
    # each forward pass reads the weights as the run stores them, made
    # from the float32 master weights by a program of their own
    store, widen = stored_fn(cfg, nm), jax.jit(c.load)
    norms = jax.jit(_norms)
    losses, g1 = [], None
    for k, batch in enumerate(batches):
        w = widen(store(master))
        loss, grads = lg(w, jnp.asarray(batch))
        del w
        new, m, v, clipped = step_fn(master, m, v, grads, k, lr_at(opt, k))
        del grads
        if not frozen:
            master = new
        losses.append(float(loss))
        if k == 0:
            g1 = {p: float(x) for p, x in norms(clipped).items()}
        del new, clipped
    del m, v
    diff = jax.jit(lambda a, b: _norms(jax.tree.map(jnp.subtract, a, b)),
                   donate_argnums=(0,))
    d = {p: float(x) for p, x in diff(master, initial(cfg, seed)).items()}
    return {"losses": losses, "g1": g1, "d3": d}


def leaf_gap(prog: dict, ref: dict, keep) -> float:
    """Largest |prog - ref| / max(ref, median ref) over the leaves kept."""
    keys = [k for k in ref if keep(k)]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def compare_train(losses, g1, d3, ref) -> dict:
    med_g = float(np.median(list(ref["g1"].values())))
    moving = lambda k: ref["g1"][k] >= TINY_GRAD * med_g
    return {
        "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"])),
        "grad_leaf_gap": leaf_gap(g1, ref["g1"], lambda k: True),
        "update_leaf_gap": leaf_gap(d3, ref["d3"], moving),
    }


def logits_fn(cfg, nm: c.Numerics):
    """jit'd (stored weights, rows (R, S), positions (R, K)) -> logits
    (R, K, V) at those positions of each row, computed a row at a time."""
    import jax
    fam = family(cfg.family)

    def fn(w, rows, at):
        return jax.lax.map(lambda ra: fam.forward(w, ra[0], cfg, nm, at=ra[1]),
                           (rows, at))

    return jax.jit(fn)


def serve_rows(prompts_tokens, served, length: int):
    """Rows of prompt + served tokens (all but the last, which nothing
    follows), padded at the end to ``length``; and for each row the
    positions whose logits predicted the served tokens."""
    rows = np.full((len(served), length), PAD, np.int32)
    at = np.zeros((len(served), len(served[0])), np.int32)
    for i, (pt, sv) in enumerate(zip(prompts_tokens, served)):
        seq = list(pt) + list(sv[:-1])
        rows[i, :len(seq)] = seq
        at[i] = len(pt) - 1 + np.arange(len(sv))
    return rows, at


def token_gaps(logits, tokens) -> np.ndarray:
    """(R, K): the best logit minus the logit of ``tokens`` (the served
    tokens, or another path's choice) at each position."""
    tokens = np.asarray(tokens)
    pick = np.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return logits.max(-1) - pick
