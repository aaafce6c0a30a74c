"""The reference's first training steps of a cell, from the seed.

``steps(config, traffic, batch, seed)`` draws the cell's traffic and
initial weights from the seed, then for each step: on a refresh step
(every ``refresh_every`` steps, from step 0) the selection inputs of
every row (``model.selection_row``, on the chip, float32) and the
selection decision
(``select.decide``, float64 on the host); on any other step the last
refresh's rows and weights, kept; then the loss and gradient of the chosen
rows weighted as decided (``model.Programs``, layer by layer), and AdamW
with global-norm clipping and the cosine schedule with linear warm-up, as
the traffic file's ``optimizer`` gives them. Weights are kept in their
stored type (bfloat16 matrices, float32 norm scales), taken to float32 for
every use and rounded back after each update; the gradient and AdamW's
moments are float32.

What it returns is what the program's run is compared with: each step's
loss and the rows it trained on (``select``; its own decisions on the
refresh steps are ``decided``), each leaf's norm of the first (clipped)
gradient, and each leaf's change over the steps. With ``follow`` (the
decisions of the run under test, one a step) each step's decision is
judged by this reference: on a refresh step by its own scores
(``select.shortfall``), on any other step by whether it kept the last
refresh's rows and rank (0 if it did, 1 if not); the step then trains on
the rows that decision chose.
A near-tie that rounding tips the other way is then judged as the
near-tie it is, and the training arithmetic is compared on the same rows.

The reference knows the traffic that ``check_traffic`` admits: the
``graft`` sampler with ``svd`` features and ``probe`` gradient
embeddings, any ``rset``, ``eps`` and ``refresh_every``, and AdamW under
the cosine schedule. A mix outside it needs its own reference here first.

``fault`` plants one of the faults the correctness check must catch, in
the reference put in the program's place: ``"unchanged"`` (the step
returns its state unchanged) or ``"half"`` (the second half of each
batch is left out, the mean taken over the rest).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.reference import model as ref
from chipbench.reference import select as sel_lib


# the traffic the reference knows: the values it can follow, by key
KNOWN_TRAFFIC = {"sampler": {"graft"}}
KNOWN_GRAFT = {"feature_mode": {"svd"}, "grad_mode": {"probe"},
               "rset": None, "eps": None, "refresh_every": None}
KNOWN_OPTIMIZER = {"name": {"adamw"}, "schedule": {"cosine"},
                   "learning_rate": None, "warmup_steps": None, "beta1": None,
                   "beta2": None, "eps": None, "weight_decay": None,
                   "clip_norm": None}


def check_traffic(traffic: Dict[str, Any]) -> None:
    """Refuse a traffic file whose selection or optimizer the reference
    does not follow (a key it does not know, or a value outside the set
    it knows), so that no run is judged against the wrong arithmetic."""
    bad = [f"{k}={traffic[k]!r}"
           for k, ok in KNOWN_TRAFFIC.items() if traffic[k] not in ok]
    for section, known in (("graft", KNOWN_GRAFT),
                           ("optimizer", KNOWN_OPTIMIZER)):
        for k, v in traffic[section].items():
            if k not in known or (known[k] is not None
                                  and v not in known[k]):
                bad.append(f"{section}.{k}={v!r}")
    if bad:
        raise ValueError("the reference does not follow "
                         + ", ".join(bad))


def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in paths]


def learning_rate(opt: Dict[str, Any], total_steps: int, step: int) -> float:
    """Linear warm-up from 0, then cosine to a tenth of the peak."""
    lr, warm = opt["learning_rate"], max(opt["warmup_steps"], 1)
    if step < warm:
        return lr * step / warm
    progress = min(max((step - warm) / max(total_steps - warm, 1), 0.0), 1.0)
    return lr * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * progress)))


def _adamw(opt: Dict[str, Any]):
    """AdamW on the stored weights: each leaf taken to float32, updated, and
    rounded back to its stored type; the moments are float32."""
    b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                       opt["weight_decay"])

    def apply(p, g, mo, ve, lr, t, clip_scale):
        def leaf(p, g, mo, ve):
            p32, g = p.astype(jnp.float32), g * clip_scale
            mo = b1 * mo + (1 - b1) * g
            ve = b2 * ve + (1 - b2) * g * g
            upd = (mo / (1 - b1 ** t)) / (jnp.sqrt(ve / (1 - b2 ** t)) + eps) \
                + wd * p32
            return (p32 - lr * upd).astype(p.dtype), mo, ve
        out = jax.tree_util.tree_map(leaf, p, g, mo, ve)
        pick = lambda i: jax.tree_util.tree_map(    # noqa: E731
            lambda x: x[i], out, is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), pick(1), pick(2)

    return jax.jit(apply, donate_argnums=(0, 2, 3))


def leaf_norms(tree) -> List[float]:
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])
    return [float(x) for x in jax.device_get(fn(tree))]


def _gradient(prog: ref.Programs, m: ref.Dims, p, tokens, labels,
              chosen, acc):
    """(weighted loss, gradient) of the ``chosen`` (row, weight) pairs,
    one row at a time, layer by layer, summed into ``acc`` (zeros)."""
    loss = 0.0
    for r, w in chosen:
        t = jnp.asarray(tokens[r])
        xs = prog.forward(p, t)
        row_loss, dx, acc = prog.head(p, xs, jnp.asarray(labels[r]),
                                      jnp.float32(w), acc)
        for i in reversed(range(m.layers)):
            dx, acc = prog.layer_back(p, np.int32(i), xs, dx, acc)
        acc = prog.embed_back(t, dx, acc)
        loss += float(row_loss)
        del xs, dx
    return loss, acc


def steps(config: Dict[str, Any], traffic: Dict[str, Any], batch: int,
          seed: int, n_steps: int = 3, f8: bool = False,
          fault: Optional[str] = None,
          follow: Optional[List[Dict[str, Any]]] = None) -> Dict[str, Any]:
    """The reference's first ``n_steps`` steps of a cell whose global batch
    is ``batch`` rows, on the first chip."""
    check_traffic(traffic)
    m = ref.Dims.from_config(config)
    K, S = batch, traffic["seq_len"]
    gr, opt = traffic["graft"], traffic["optimizer"]
    every = gr.get("refresh_every", 1)
    stride = max(1, S // traffic["probe_positions"])
    pool = traffic_lib.make_pool(m.vocab, S, K, traffic["pool_steps"], seed,
                                 traffic["num_clusters"],
                                 traffic["cluster_stickiness"])
    prog = ref.programs(m, stride, f8)
    init = jax.jit(lambda k: ref.init(m, k))
    key = jax.random.PRNGKey(seed)
    p = init(key)
    names = leaf_names(p)
    moments = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t))
    mo, ve = moments(p), moments(p)
    adamw = _adamw(opt)
    out: Dict[str, Any] = {"losses": [], "select": [], "decided": [],
                           "margins": [], "shortfalls": []}
    kept: Dict[str, Any] = {}
    for t in range(n_steps):
        tokens = pool["tokens"][t % traffic["pool_steps"]]
        labels = pool["labels"][t % traffic["pool_steps"]]
        if t % every == 0:
            rows = K // 2 if fault == "half" else K
            A, E = jax.device_get(prog.select(p, tokens[:rows],
                                              labels[:rows]))
            dec = sel_lib.decide(A, E, gr["rset"], gr["eps"])
            dec = {k: dec[k] for k in ("pivots", "rank", "weights",
                                       "pivot_margin", "rank_margin")}
            out["decided"].append({k: dec[k] for k in ("pivots", "rank")})
            out["margins"].append({k: dec[k] for k in ("pivot_margin",
                                                       "rank_margin")})
            if follow is not None:
                # judge the decision under test by this step's own scores
                out["shortfalls"].append(sel_lib.shortfall(
                    A, E, gr["rset"], gr["eps"], follow[t]["pivots"],
                    int(follow[t]["rank"])))
            kept = follow[t] if follow is not None else dec
        elif follow is not None:
            # no refresh: the decision under test must keep the last one
            fr = int(kept["rank"])
            same = int(follow[t]["rank"]) == fr and [
                int(r) for r in follow[t]["pivots"][:fr]] == [
                int(r) for r in kept["pivots"][:fr]]
            out["shortfalls"].append(0.0 if same else 1.0)
            kept = follow[t]
        out["select"].append({k: kept[k] for k in ("pivots", "rank",
                                                   "weights")})
        # train on the kept rows, weighted by their rank
        fr = int(kept["rank"])
        chosen = [(int(r), 1.0 / fr) for r in kept["pivots"][:fr]]
        loss, g = _gradient(prog, m, p, tokens, labels, chosen, moments(p))
        out["losses"].append(loss)
        norms = leaf_norms(g)
        gnorm = math.sqrt(sum(v * v for v in norms))
        clip = opt["clip_norm"]
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
        if t == 0:
            out["grad_norms"] = {n_: v * scale for n_, v in zip(names, norms)}
        if fault != "unchanged":
            lr = learning_rate(opt, traffic["train_steps"], t)
            p, mo, ve = adamw(p, g, mo, ve, jnp.float32(lr),
                              jnp.float32(t + 1), jnp.float32(scale))
        del g
    if fault == "unchanged":
        out["grad_norms"] = {n_: 0.0 for n_ in names}
    del mo, ve
    p0 = init(key)
    diff = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))) for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))])
    out["change_norms"] = {n_: float(v) for n_, v in
                           zip(names, jax.device_get(diff(p, p0)))}
    return out
