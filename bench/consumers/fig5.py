"""Consumer ``fig5``: the scDataset paper's Fig. 5 step (arXiv:2506.01883).

``log1p`` of the counts, four linear heads over every gene (cell line 50,
drug 380, broad MoA 4, fine MoA 27), the sum of the heads' mean softmax
cross-entropies, and one Adam update -- as in the repository's
``benchmarks/bench_fig5_classification.py``: parameters and Adam state in
float32, matmuls at JAX's default precision (on the TPU, one pass with
inputs rounded to bfloat16).  The learning rate is the paper's 1e-5, not
that file's 1e-2: at 62,710 genes Adam's first steps move each weight by
about the learning rate, ~3k nonzero features add those moves up, and at
1e-2 the logits reach ~30 within two steps (loss 14.8 -> 48.6 -> 58.9 on
one TPU v5e).  The heads start from N(0, 0.01^2), made from the seed on
the device in one call.

``reference_steps`` is the same arithmetic in float64 numpy, with the
gradients written out by hand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TASKS = {"cell_line": 50, "drug": 380, "moa_broad": 4, "moa_fine": 27}
LR = 1e-5  # the paper's (arXiv:2506.01883, Fig. 5)
B1, B2, EPS = 0.9, 0.999, 1e-8
INIT_SCALE = 0.01
STEP_NAME = "fig5_step"  # the device trace's module is jit_fig5_step
# Limits of the numbers ``compare`` gives, each between the largest reading
# of sound runs on a dozen seeds and more and the smallest of the bfloat16
# control or of a planted fault, on one TPU v5e at the cells' size; the
# readings they were set from are in PERF.md, section 4.
LIMITS = {"loss_rel_gap": 5e-4, "grad1_norm_gap": 2e-2, "change3_norm_gap": 3.5e-4}
VARIANTS = ("program", "control_bf16", "state_unchanged", "half_batch")


def init(key_seed: int, n_genes: int):
    """Heads and Adam state, made on the device in one jitted call."""

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(TASKS))
        heads = {t: {"w": INIT_SCALE * jax.random.normal(k, (n_genes, c), jnp.float32),
                     "b": jnp.zeros((c,), jnp.float32)}
                 for (t, c), k in zip(TASKS.items(), keys)}
        zeros = jax.tree.map(jnp.zeros_like, heads)
        return heads, {"m": zeros, "v": zeros, "count": jnp.zeros((), jnp.int32)}

    return make(jax.random.PRNGKey(key_seed))


def make_step(variant: str = "program"):
    """The jitted step ``(heads, opt, batch) -> (heads, opt, loss)``.

    ``variant`` other than ``"program"`` breaks the step for the limit
    readings and the tests: ``control_bf16`` computes it in bfloat16,
    ``state_unchanged`` returns the heads it was given, ``half_batch``
    takes the mean over the first half of the rows only."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; expected one of {VARIANTS}")
    dtype = jnp.bfloat16 if variant == "control_bf16" else jnp.float32

    def fig5_step(heads, opt, batch):
        with jax.named_scope("fig5"):
            x = jnp.log1p(batch["x"]).astype(dtype)
            ys = {t: batch[t] for t in TASKS}
            if variant == "half_batch":
                half = x.shape[0] // 2
                x, ys = x[:half], {t: y[:half] for t, y in ys.items()}
            params = jax.tree.map(lambda a: a.astype(dtype), heads)

            def loss_fn(params):
                total = jnp.zeros((), dtype)
                for t in TASKS:
                    logits = x @ params[t]["w"] + params[t]["b"]
                    lse = jax.nn.logsumexp(logits, axis=-1)
                    gold = jnp.take_along_axis(logits, ys[t][:, None], axis=-1)[:, 0]
                    total = total + jnp.mean(lse - gold)
                return total

            loss, grads = jax.value_and_grad(loss_fn)(params)
            m_dt = jax.tree.map(lambda a: a.astype(dtype), opt["m"])
            v_dt = jax.tree.map(lambda a: a.astype(dtype), opt["v"])
            cnt = opt["count"] + 1
            new_m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m_dt, grads)
            new_v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v_dt, grads)
            c1 = (1 - B1 ** cnt.astype(jnp.float32)).astype(dtype)
            c2 = (1 - B2 ** cnt.astype(jnp.float32)).astype(dtype)
            new = jax.tree.map(lambda p, m, v: p - LR * (m / c1) / (jnp.sqrt(v / c2) + EPS),
                               params, new_m, new_v)
            f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
            if variant == "state_unchanged":
                new = params
            return (f32(new), {"m": f32(new_m), "v": f32(new_v), "count": cnt},
                    loss.astype(jnp.float32))

    fig5_step.__name__ = STEP_NAME
    return jax.jit(fig5_step)


# ------------------------------------------------------------------ reference
def _loss_and_grads(heads: dict, x: np.ndarray, ys: dict):
    loss, grads = 0.0, {}
    for t in TASKS:
        w, b = heads[t]["w"], heads[t]["b"]
        logits = x @ w + b
        top = logits.max(axis=1, keepdims=True)
        e = np.exp(logits - top)
        s = e.sum(axis=1, keepdims=True)
        rows = np.arange(len(x))
        loss += float(np.mean(np.log(s[:, 0]) + top[:, 0] - logits[rows, ys[t]]))
        d = e / s
        d[rows, ys[t]] -= 1.0
        d /= len(x)
        grads[t] = {"w": x.T @ d, "b": d.sum(axis=0)}
    return loss, grads


def reference_steps(heads0: dict, batches: list) -> dict:
    """Float64 losses, first gradient and change of the heads over ``batches``
    (each a dict of dense counts ``x`` and the label columns)."""
    heads = {t: {k: np.asarray(v, np.float64) for k, v in h.items()} for t, h in heads0.items()}
    m = {t: {k: np.zeros_like(v) for k, v in h.items()} for t, h in heads.items()}
    v = {t: {k: np.zeros_like(a) for k, a in h.items()} for t, h in heads.items()}
    losses, first_grad = [], None
    for i, b in enumerate(batches, start=1):
        x = np.log1p(b["x"].astype(np.float64))
        loss, grads = _loss_and_grads(heads, x, {t: np.asarray(b[t]) for t in TASKS})
        losses.append(loss)
        if first_grad is None:
            first_grad = grads
        c1, c2 = 1 - B1 ** i, 1 - B2 ** i
        for t in TASKS:
            for k in ("w", "b"):  # in place: these are ~29M float64 entries
                g = grads[t][k]
                m[t][k] *= B1
                m[t][k] += (1 - B1) * g
                v[t][k] *= B2
                v[t][k] += (1 - B2) * (g * g)
                denom = np.sqrt(v[t][k] / c2)
                denom += EPS
                step = m[t][k] / c1
                step /= denom
                step *= LR
                heads[t][k] -= step
    change = {t: {k: heads[t][k] - np.asarray(heads0[t][k], np.float64) for k in ("w", "b")}
              for t in TASKS}
    return {"losses": losses, "grad1": first_grad, "change": change}


def program_readings(heads0, opt1, heads3, losses) -> dict:
    """The program's side of the comparison, on the host: the first
    gradient as Adam holds it after step 1 (m = (1 - b1) g), and the change
    of the heads after step 3."""
    grad1 = {t: {k: np.asarray(opt1["m"][t][k], np.float64) / (1 - B1) for k in ("w", "b")}
             for t in TASKS}
    change = {t: {k: np.asarray(heads3[t][k], np.float64) - np.asarray(heads0[t][k], np.float64)
                  for k in ("w", "b")} for t in TASKS}
    return {"losses": [float(l) for l in losses], "grad1": grad1, "change": change}


def _leaf_norms(tree: dict) -> dict:
    return {(t, k): float(np.linalg.norm(tree[t][k])) for t in TASKS for k in ("w", "b")}


def _worst_leaf_gap(prog: dict, ref: dict, leaves) -> float:
    """Largest |norm(program leaf) - norm(reference leaf)| over ``leaves``,
    each against its reference norm or the median leaf's, whichever is larger."""
    p, r = _leaf_norms(prog), _leaf_norms(ref)
    floor = float(np.median(list(r.values())))
    return max(abs(p[k] - r[k]) / max(r[k], floor) for k in leaves)


def compare(prog: dict, ref: dict) -> dict:
    """The consumer's numbers for ``correct``: the worst relative gap of the
    three losses, and of the first gradient's and the three steps' change's
    leaf norms.  Leaves whose reference gradient is under a thousandth of
    the median leaf's would move by round-off alone, so the change leaves
    them out."""
    g = _leaf_norms(ref["grad1"])
    med = float(np.median(list(g.values())))
    moving = [k for k, n in g.items() if n >= 1e-3 * med]
    return {
        "loss_rel_gap": max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad1_norm_gap": _worst_leaf_gap(prog["grad1"], ref["grad1"], list(g)),
        "change3_norm_gap": _worst_leaf_gap(prog["change"], ref["change"], moving),
    }
