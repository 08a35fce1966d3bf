"""Plain float32 reference of what a training cell's round computes.

Written from the configuration file alone, in ``jax.numpy``: the pieces
the families share (GQA attention with RoPE or M-RoPE, SwiGLU MLP,
RMSNorm), the embedding and the cross-entropy loss, plain SGD, and the
Hier-AVG schedule: each plan level averages its group of learners every
``period`` steps, inner levels first, a ``qint8`` level after each
learner's blockwise int8 round trip.  Each family's layers, weights and
any loss term of its own are its module under ``families/``
(``families.of``).

It imports nothing of the program (only the benchmark's own counts).
Weights and rows are made from the seed by the recipe the
configuration's training job documents (``jax.random`` calls in a fixed
order), so both sides start from the same numbers without either
handing the other an array.

Memory: every layer is rematerialised, attention is taken in blocks of
queries and the loss in blocks of positions, so one learner's round fits
where the program's does.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from chipbench import families
from chipbench.counts import padded_vocab, vision_positions

Params = Dict[str, Any]

Q_BLOCK = 512        # queries per attention block
LOSS_BLOCK = 512     # positions per block of the head and loss


# --------------------------------------------------------------------- #
# the configuration and the job, as the files state them
# --------------------------------------------------------------------- #

class Level(NamedTuple):
    name: str        # local | pod | global
    period: int      # SGD steps between two of its reductions
    codec: str       # "" (dense mean) or "qint8:<block>"


class Job(NamedTuple):
    learners: int
    s: int           # learners per group
    batch: int       # sequences per learner step
    seq: int         # positions per sequence
    lr: float
    levels: Tuple[Level, ...]

    @property
    def steps_per_round(self) -> int:
        return self.levels[-1].period

    @property
    def groups(self) -> int:
        return self.learners // self.s


def parse_plan(spec: str) -> Tuple[Level, ...]:
    """``local@1:qint8:128/global@8`` -> levels, innermost first."""
    levels = []
    for part in spec.split("/"):
        name, rest = part.split("@", 1)
        period, _, codec = rest.partition(":")
        if codec and not codec.startswith("qint8"):
            raise ValueError(f"the reference has no codec {codec!r}")
        levels.append(Level(name, int(period), codec))
    for inner, outer in zip(levels, levels[1:]):
        if outer.period % inner.period:
            raise ValueError(f"plan {spec!r}: periods must nest")
    return tuple(levels)


def job_from(traffic: Dict[str, Any]) -> Job:
    return Job(learners=int(traffic["learners"]), s=int(traffic["s"]),
               batch=int(traffic["batch"]), seq=int(traffic["seq"]),
               lr=float(traffic["lr"]), levels=parse_plan(traffic["plan"]))


# --------------------------------------------------------------------- #
# weights and rows from the seed
# --------------------------------------------------------------------- #

def linear_init(key, d_in, d_out):
    return (1.0 / math.sqrt(d_in)) * jax.random.truncated_normal(
        key, -2.0, 2.0, (d_in, d_out), jnp.float32)


def gqa_init(key, cfg):
    d, h, kv, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    k = jax.random.split(key, 4)
    return {"wq": linear_init(k[0], d, h * hd),
            "wk": linear_init(k[1], d, kv * hd),
            "wv": linear_init(k[2], d, kv * hd),
            "wo": linear_init(k[3], h * hd, d)}


def mlp_init(key, d, f):
    k = jax.random.split(key, 3)
    return {"w_gate": linear_init(k[0], d, f), "w_up": linear_init(k[1], d, f),
            "w_down": linear_init(k[2], f, d)}


def norm_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def embed_init(key, cfg):
    """The embedding and the final norm; the family adds the rest."""
    d = cfg["d_model"]
    return {"embed": jax.random.normal(key, (padded_vocab(cfg), d))
            / math.sqrt(d), "final_norm": norm_init(d)}


def init_params(cfg, seed: int) -> Params:
    """One learner's initial weights; every learner starts from them."""
    return families.of(cfg).init(jax.random.PRNGKey(seed), cfg)


def _mrope_positions(nv: int, st: int):
    side = int(round(nv ** 0.5))
    while nv % side:
        side -= 1
    grid = (1, side, nv // side)
    tt, hh, ww = jnp.meshgrid(*(jnp.arange(g) for g in grid), indexing="ij")
    vis = jnp.stack([tt.ravel(), hh.ravel(), ww.ravel()], -1)
    txt = max(grid) + jnp.arange(st)
    return jnp.concatenate([vis, jnp.stack([txt] * 3, -1)]).astype(jnp.int32)


def _rows(key, cfg, batch: int, seq: int):
    k1, k2, k3 = jax.random.split(key, 3)
    v = cfg["vocab_size"]
    nv = vision_positions(cfg, seq)
    st = seq - nv
    out = {"tokens": jax.random.randint(k1, (batch, st), 0, v),
           "labels": jax.random.randint(k2, (batch, st), 0, v)}
    if nv:
        out["vision_embeds"] = 0.02 * jax.random.normal(
            k3, (batch, nv, cfg["d_model"]))
    return out


def round_rows(cfg, job: Job, seed: int, r: int):
    """Round ``r``'s rows: leaves ``[steps, learners, batch, ...]``, one
    independent key per (step, learner)."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), r)
    n = job.steps_per_round * job.learners
    cells = [_rows(k, cfg, job.batch, job.seq)
             for k in jax.random.split(key, n)]
    return jax.tree.map(
        lambda *xs: jnp.stack(xs).reshape(
            (job.steps_per_round, job.learners) + xs[0].shape), *cells)


# --------------------------------------------------------------------- #
# the model
# --------------------------------------------------------------------- #

def rms(p, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def _inv_freq(hd, theta):
    return 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)


def _rope_angles(cfg, seq: int):
    """[seq, head_dim/2] rotation angles: plain RoPE over 0..seq-1, or
    M-RoPE, whose frequency sections follow the (t, h, w) coordinates."""
    inv = _inv_freq(cfg["head_dim"], cfg["rope_theta"])
    nv = vision_positions(cfg, seq)
    if not cfg.get("mrope_sections"):
        return jnp.arange(seq, dtype=jnp.float32)[:, None] * inv
    pos = _mrope_positions(nv, seq - nv).astype(jnp.float32)   # [S, 3]
    coord = jnp.concatenate([jnp.full((n,), i) for i, n in
                             enumerate(cfg["mrope_sections"])])
    return pos[:, coord] * inv


def _rotate(x, ang):
    """x [B, S, H, D]; split-halves rotation by ang [S, D/2]."""
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


def attention(p, x, ang, cfg, window: int):
    b, s, _ = x.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = _rotate((x @ p["wq"]).reshape(b, s, h, hd), ang)
    k = _rotate((x @ p["wk"]).reshape(b, s, kv, hd), ang)
    v = (x @ p["wv"]).reshape(b, s, kv, hd)
    # query head i reads key/value head i // (h / kv)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def block(q_blk, start):
        qpos = start + jnp.arange(q_blk.shape[1])
        see = kpos[None] <= qpos[:, None]
        if window:
            see &= qpos[:, None] - kpos[None] < window
        sc = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k).astype(jnp.float32)
        sc = jnp.where(see, sc / math.sqrt(hd), -jnp.inf)
        pr = jax.nn.softmax(sc, -1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", pr, v)

    qb = min(Q_BLOCK, s)
    out = jnp.concatenate([block(q[:, i:i + qb], i)
                           for i in range(0, s, qb)], 1)
    return out.reshape(b, s, h * hd) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def layer_stack(params: Params, x, cfg):
    """The stack of a family of one kind of layer: ``params["layers"]``
    scanned through its ``block`` under RoPE (or M-RoPE) angles, with no
    loss term of its own."""
    ang = _rope_angles(cfg, x.shape[1])
    block = families.of(cfg).block

    def layer(x, lp):
        return jax.checkpoint(lambda x, lp: block(lp, x, ang, cfg))(x, lp), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return x, 0.0


def hidden(params: Params, rows, cfg):
    """Final-norm hidden states of the positions that carry a label, and
    the family's loss term (``families/__init__.py``)."""
    x = params["embed"][rows["tokens"]]
    nv = 0
    if "vision_embeds" in rows:
        nv = rows["vision_embeds"].shape[1]
        x = jnp.concatenate([rows["vision_embeds"].astype(x.dtype), x], 1)
    stack = getattr(families.of(cfg), "stack", layer_stack)
    x, extra = stack(params, x, cfg)
    return rms(params["final_norm"], x, cfg["norm_eps"])[:, nv:], extra


def mean_nll(params: Params, h, labels) -> jax.Array:
    """Mean cross-entropy of the head over ``h``, in blocks of positions."""
    head = params["lm_head"] if "lm_head" in params else params["embed"].T

    @jax.checkpoint
    def nll(hb, lb):
        logits = (hb @ head).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    n = h.shape[1]
    blk = min(LOSS_BLOCK, n)
    total = sum(nll(h[:, i:i + blk], labels[:, i:i + blk])
                for i in range(0, n, blk))
    return total / labels.size


def loss(params: Params, rows, cfg) -> jax.Array:
    """Mean next-token cross-entropy of one learner's rows, plus the
    family's loss term."""
    h, extra = hidden(params, rows, cfg)
    return mean_nll(params, h, rows["labels"]) + extra


# --------------------------------------------------------------------- #
# the Hier-AVG round
# --------------------------------------------------------------------- #

def qint8_round_trip(x, block: int):
    """Blockwise absmax int8 quantisation of one learner's leaf, and back."""
    flat = x.reshape(-1)
    nb = -(-flat.size // block)
    xb = jnp.pad(flat, (0, nb * block - flat.size)).reshape(nb, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xb), -1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(xb / scale), -127, 127)
    return (q * scale).reshape(-1)[:flat.size].reshape(x.shape)


def _group_mean(x, level: Level, job: Job):
    """Mean over ``level``'s group, for leaves ``[learners, ...]``."""
    g = x.reshape((1, job.groups, job.s) + x.shape[1:])
    axes = {"local": (2,), "pod": (1, 2), "global": (0, 1, 2)}[level.name]
    m = jnp.mean(g, axes, keepdims=True)
    return jnp.broadcast_to(m, g.shape).reshape(x.shape)


def reduce_level(params, level: Level, job: Job, exchange: bool = True):
    if level.codec:
        block = int(level.codec.split(":")[1])
        params = jax.tree.map(
            jax.vmap(lambda leaf: qint8_round_trip(leaf, block)), params)
    if not exchange:
        return params
    return jax.tree.map(lambda x: _group_mean(x, level, job), params)


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """Float32 norm of each leaf, keyed by its path."""
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in
        jax.tree_util.tree_flatten_with_path(tree)[0]}


class Trainer:
    """The reference's Hier-AVG rounds over ``[learners, ...]`` weights.

    ``dtype`` other than float32 makes the control: weights, activations
    and the update held in it.  ``exchange=False`` (no group means) and
    ``loss_fn`` (another loss) plant faults.  ``sharding``, when given,
    places the learner axis, one learner per device."""

    def __init__(self, cfg, job: Job, *, dtype=jnp.float32,
                 exchange: bool = True, loss_fn=loss, sharding=None):
        self.cfg, self.job, self.dtype = cfg, job, jnp.dtype(dtype)
        grad = jax.vmap(jax.value_and_grad(lambda p, r: loss_fn(p, r, cfg)))

        def step(params, rows):
            rows = jax.tree.map(self._cast, rows)
            losses, g = grad(params, rows)
            params = jax.tree.map(
                lambda p, gr: (p.astype(jnp.float32) - job.lr
                               * gr.astype(jnp.float32)).astype(p.dtype),
                params, g)
            return params, losses, leaf_norms(jax.tree.map(lambda x: x[0], g))

        kw = {}
        if sharding is not None:
            kw = dict(in_shardings=(sharding, sharding),
                      out_shardings=(sharding, None, None))
        self._step = jax.jit(step, donate_argnums=(0,), **kw)
        self._reduce = {
            lvl.name: jax.jit(
                lambda p, lvl=lvl: reduce_level(p, lvl, job, exchange),
                donate_argnums=(0,),
                **({} if sharding is None else
                   dict(in_shardings=sharding, out_shardings=sharding)))
            for lvl in job.levels}
        self._sharding = sharding

    def _cast(self, x):
        return x.astype(self.dtype) if jnp.issubdtype(
            x.dtype, jnp.floating) else x

    def start(self, params1: Params) -> Params:
        """Every learner from the same weights, in the trainer's dtype."""
        n = self.job.learners
        out = jax.tree.map(lambda p: jnp.broadcast_to(
            self._cast(p)[None], (n,) + p.shape), params1)
        if self._sharding is not None:
            out = jax.device_put(out, self._sharding)
        return out

    def run_round(self, params, rows):
        """-> (params, mean loss over steps and learners, per-leaf norms
        of learner 0's first gradient)."""
        losses, first = [], None
        for t in range(self.job.steps_per_round):
            rows_t = jax.tree.map(lambda r: r[t], rows)
            if self._sharding is not None:
                rows_t = jax.device_put(rows_t, self._sharding)
            params, l, gn = self._step(params, rows_t)
            first = gn if first is None else first
            losses.append(l)
            for level in self.job.levels:
                if (t + 1) % level.period == 0:
                    params = self._reduce[level.name](params)
        mean = jnp.mean(jnp.stack(losses).astype(jnp.float32))
        return params, mean, first
