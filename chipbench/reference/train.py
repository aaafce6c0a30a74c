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

The chip holds the weights, the chosen rows' activations and one part of
the float32 state at a time; the gradient and AdamW's moments wait on the
host between uses. Every float32 operation on a leaf is the one a
whole-model step would make, in the same order (``_gradient``,
``_update``): on the CPU the readings are those of a whole-model step bit
for bit (``tests/test_blocks.py``). On a TPU, XLA compiles a layer's
backward into other code when it sums into one layer's gradient instead of
the stacked whole, and the gradient can differ in its last bits.

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
    rounded back to its stored type; the moments are float32. Two programs:
    for a whole leaf, and for layer ``i`` of a stacked one."""
    b1, b2, eps, wd = (opt["beta1"], opt["beta2"], opt["eps"],
                       opt["weight_decay"])

    def leaf(p, g, mo, ve, lr, t, clip_scale):
        p32, g = p.astype(jnp.float32), g * clip_scale
        mo = b1 * mo + (1 - b1) * g
        ve = b2 * ve + (1 - b2) * g * g
        upd = (mo / (1 - b1 ** t)) / (jnp.sqrt(ve / (1 - b2 ** t)) + eps) \
            + wd * p32
        return (p32 - lr * upd).astype(p.dtype), mo, ve

    def one_layer(stack, i, g, mo, ve, lr, t, clip_scale):
        p, mo, ve = leaf(jax.lax.dynamic_index_in_dim(stack, i, keepdims=False),
                         g, mo, ve, lr, t, clip_scale)
        return jax.lax.dynamic_update_index_in_dim(stack, p, i, 0), mo, ve

    return (jax.jit(leaf, donate_argnums=(0, 2, 3)),
            jax.jit(one_layer, donate_argnums=(0, 3, 4)))


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree_util.tree_leaves(tree)]


def leaf_norms(tree) -> List[float]:
    return [float(x) for x in jax.device_get(_norms(tree))]


def _zeros(tree, drop: int = 0):
    """Float32 zeros of each leaf's shape, less its first ``drop`` axes."""
    return jax.tree_util.tree_map(
        lambda w: jnp.zeros(w.shape[drop:], jnp.float32), tree)


def _gradient(prog: ref.Programs, m: ref.Dims, p, tokens, labels, chosen):
    """(weighted loss, gradient) of the ``chosen`` (row, weight) pairs; the
    gradient a tree of the weights' structure, in float32 on the host.

    The rows' forwards run first, each keeping its layer inputs; then each
    row's head; then each layer from the last, over every row in turn; then
    the embedding. A leaf's gradient is summed over the rows in their order,
    starting from zeros, as one row at a time would sum it (a tied head's
    share and each row's embedding rows alternate, row by row). The chip
    holds one part of the gradient at a time: the final norm's with an
    untied head's, one layer's, the embedding's."""
    rows = [(jnp.asarray(tokens[r]), jnp.asarray(labels[r]), jnp.float32(w))
            for r, w in chosen]
    xs = [prog.forward(p, t) for t, _, _ in rows]
    part = {"final_norm": _zeros(p["final_norm"])}
    if not m.tied:
        part["lm_head"] = _zeros(p["lm_head"])
    loss, dxs = 0.0, []
    for (_, lab, w), x in zip(rows, xs):
        row_loss, dx, part = prog.head(p, x, lab, w, part)
        loss += float(row_loss)
        dxs.append(dx)
    grad = jax.device_get(part)
    grad["blocks"] = jax.tree_util.tree_map(
        lambda w: np.empty(w.shape, np.float32), p["blocks"])
    host_layers = jax.tree_util.tree_leaves(grad["blocks"])
    done = []           # a layer's gradient, copied out while the next runs
    for i in reversed(range(m.layers)):
        acc = _zeros(p["blocks"], drop=1)
        for j, x in enumerate(xs):
            dxs[j], acc = prog.layer_back(p, np.int32(i), x, dxs[j], acc)
        _to_host(done)
        done = [(host, i, dev) for host, dev in
                zip(host_layers, jax.tree_util.tree_leaves(acc))]
        for _, _, dev in done:
            dev.copy_to_host_async()
        del acc
    _to_host(done)
    part = {"embed": _zeros(p["embed"])}
    for (t, lab, w), x, dx in zip(rows, xs, dxs):
        if m.tied:
            part = prog.head(p, x, lab, w, part)[2]
        part = prog.embed_back(t, dx, part)
    grad["embed"] = jax.device_get(part["embed"])
    del xs, dxs, part
    return loss, grad


def _to_host(copies: list) -> None:
    """``host[at] = dev`` for each (host, at, dev), then forget them; a
    ``None`` host only waits for ``dev``."""
    for host, at, dev in copies:
        if host is None:
            dev.block_until_ready()
        else:
            host[at] = np.asarray(dev)
    copies.clear()


def _update(adamw, leaves: List[Any], stacked: List[bool],
            grad: List[np.ndarray], mo: List[Optional[np.ndarray]],
            ve: List[Optional[np.ndarray]], lr, t, scale,
            keep: bool) -> None:
    """AdamW on the device weights ``leaves``, one leaf, or one layer of a
    stacked leaf, at a time: its gradient and moments moved in from the
    host (zeros on the first step), and the moments moved back when
    ``keep``. One slice runs while the last one's moments are copied out;
    no more are queued, so the chip holds two slices' state at most."""
    whole, one_layer = adamw
    done: list = []
    for j, g in enumerate(grad):
        first = mo[j] is None
        if keep and first:
            mo[j], ve[j] = np.empty(g.shape, np.float32), \
                np.empty(g.shape, np.float32)
        for i in range(g.shape[0]) if stacked[j] else [None]:
            at = slice(None) if i is None else i
            gi = g[at]
            if first:
                m0 = jnp.zeros(gi.shape, jnp.float32)
                v0 = jnp.zeros(gi.shape, jnp.float32)
            else:
                m0, v0 = mo[j][at], ve[j][at]
            if i is None:
                leaves[j], m1, v1 = whole(leaves[j], gi, m0, v0, lr, t, scale)
            else:
                leaves[j], m1, v1 = one_layer(leaves[j], np.int32(i), gi, m0,
                                              v0, lr, t, scale)
            _to_host(done)
            done = [(mo[j] if keep else None, at, m1),
                    (ve[j] if keep else None, at, v1)]
            if keep:
                m1.copy_to_host_async()
                v1.copy_to_host_async()
            del m1, v1
    _to_host(done)


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
    p0 = jax.device_get(p)          # the initial weights, kept on the host
    names = leaf_names(p)
    leaves, treedef = jax.tree_util.tree_flatten(p)
    stacked = [n_.split("/")[0] == "blocks" for n_ in names]
    mo: List[Optional[np.ndarray]] = [None] * len(names)
    ve: List[Optional[np.ndarray]] = [None] * len(names)
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
        loss, g = _gradient(prog, m, p, tokens, labels, chosen)
        out["losses"].append(loss)
        g = jax.tree_util.tree_leaves(g)
        norms = [leaf_norms([x])[0] for x in g]      # one leaf at a time
        gnorm = math.sqrt(sum(v * v for v in norms))
        clip = opt["clip_norm"]
        scale = min(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
        if t == 0:
            out["grad_norms"] = {n_: v * scale for n_, v in zip(names, norms)}
        if fault != "unchanged":
            lr = learning_rate(opt, traffic["train_steps"], t)
            del p
            _update(adamw, leaves, stacked, g, mo, ve, jnp.float32(lr),
                    jnp.float32(t + 1), jnp.float32(scale),
                    keep=t + 1 < n_steps)
            p = jax.tree_util.tree_unflatten(treedef, leaves)
        del g
    if fault == "unchanged":
        out["grad_norms"] = {n_: 0.0 for n_ in names}
    del mo, ve
    diff = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))))
    out["change_norms"] = {n_: float(diff(w, w0)) for n_, w, w0 in zip(
        names, leaves, jax.tree_util.tree_leaves(p0))}
    return out
