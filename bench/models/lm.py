"""Decoder-only transformers of the Llama kind: RMSNorm, RoPE, grouped-query
causal attention, a SwiGLU MLP and a head tied to the embedding.

Weights are kept in the layout of ``repro.models.lm`` for a stack of one
repeated attention + MLP block (layers stacked on a leading axis):

    {"embed": {"emb": (V, d)}, "final_norm": {"scale": (d,)},
     "segments": [({"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
                    "attn": {"wq"|"wk"|"wv"|"wo": {"w": (L, out, in)}},
                    "mlp": {"gate"|"up"|"down": {"w": (L, out, in)}}},)]}

RMSNorm multiplies by ``1 + scale`` (the program's parameterisation of the
norm weight) with ``eps`` from the configuration; RoPE rotates halves
(``rotate_half``) at base ``rope_theta``.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.harness import span
from bench.numerics import Numerics, adamw, clip_by_global_norm, learning_rate, leaf_norms

ATTN = ("wq", "wk", "wv", "wo")
MLP = ("gate", "up", "down")


def shapes(c: dict):
    d, h, kv, dh, f = c["d_model"], c["n_heads"], c["n_kv"], c["d_head"], c["d_ff"]
    return {"wq": (h * dh, d), "wk": (kv * dh, d), "wv": (kv * dh, d),
            "wo": (d, h * dh), "gate": (f, d), "up": (f, d), "down": (d, f)}


def arch(c: dict):
    """The program's ``ArchConfig`` for this configuration: the registry's
    entry with every size set from the file."""
    from repro.configs import get_config
    from repro.models.blocks import BlockCfg

    return get_config(c["program_config"]).replace(
        d_model=c["d_model"], n_heads=c["n_heads"], n_kv=c["n_kv"],
        d_head=c["d_head"], d_ff=c["d_ff"], vocab=c["vocab"],
        segments=((c["n_layers"], (BlockCfg("attn", "mlp"),)),),
        tie_embeddings=True, activation="silu", rope_theta=c["rope_theta"],
        param_dtype=c["dtype"], compute_dtype=c["dtype"], remat=c["remat"],
        attn_chunk=c["attn_chunk"],
    )


def init_params(c: dict, key, dtype):
    """Seeded weights on the device in one call: ``N(0, 1/in)`` projections,
    ``N(0, 0.02^2)`` embedding, ``N(0, 0.1^2)`` norm scales."""
    L, d, V = c["n_layers"], c["d_model"], c["vocab"]
    sh = shapes(c)

    def make(key):
        ks = iter(jax.random.split(key, 16))
        nrm = lambda shape, std: (std * jax.random.normal(next(ks), shape, jnp.float32)).astype(dtype)
        block = {
            "ln1": {"scale": nrm((L, d), 0.1)},
            "ln2": {"scale": nrm((L, d), 0.1)},
            "attn": {n: {"w": nrm((L, *sh[n]), sh[n][1] ** -0.5)} for n in ATTN},
            "mlp": {n: {"w": nrm((L, *sh[n]), sh[n][1] ** -0.5)} for n in MLP},
        }
        return {"embed": {"emb": nrm((V, d), 0.02)},
                "final_norm": {"scale": nrm((d,), 0.1)},
                "segments": [(block,)]}

    return jax.jit(make)(key)


def check_layout(c: dict, params) -> None:
    """Fail early if the program's parameter layout moved."""
    from repro.models import lm

    want = jax.eval_shape(lambda: lm.init_lm(jax.random.PRNGKey(0), arch(c)))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise ValueError("the program's parameter layout differs from the benchmark's")


# -- work ---------------------------------------------------------------------


def elem_bytes(c: dict) -> int:
    return jnp.dtype(c["dtype"]).itemsize


def train_gemms(c: dict, w: dict) -> List[counts.Work]:
    return counts.lm_train_gemms(c, w["batch"], w["seq"], elem_bytes(c))


# -- plain reference ------------------------------------------------------------


def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x: (b, S, heads, dh), positions 0..S-1, rotate_half convention."""
    S, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _layer(c, nm: Numerics, x, p):
    b, S, _ = x.shape
    h, kv, dh = c["n_heads"], c["n_kv"], c["d_head"]
    g = h // kv
    y = _rmsnorm(x, p["ln1"]["scale"], c["norm_eps"])
    q = nm.linear(y, p["attn"]["wq"]["w"]).reshape(b, S, h, dh)
    k = nm.linear(y, p["attn"]["wk"]["w"]).reshape(b, S, kv, dh)
    v = nm.linear(y, p["attn"]["wv"]["w"]).reshape(b, S, kv, dh)
    q = _rope(q, c["rope_theta"]).reshape(b, S, kv, g, dh) * (dh ** -0.5)
    k = _rope(k, c["rope_theta"])
    s = nm.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1).astype(nm.dtype)
    o = nm.einsum("bkgqs,bskd->bqkgd", pr, v).reshape(b, S, h * dh)
    x = x + nm.linear(o, p["attn"]["wo"]["w"])
    y = _rmsnorm(x, p["ln2"]["scale"], c["norm_eps"])
    gate = nm.linear(y, p["mlp"]["gate"]["w"])
    up = nm.linear(y, p["mlp"]["up"]["w"])
    return x + nm.linear(jax.nn.silu(gate) * up, p["mlp"]["down"]["w"])


def ref_hidden(c, params, tokens, nm: Numerics):
    """Final normed hidden states of a batch of sequences (b, S)."""
    x = params["embed"]["emb"][tokens].astype(nm.dtype)
    body = jax.checkpoint(lambda x, p: (_layer(c, nm, x, p), None))
    x, _ = jax.lax.scan(body, x, params["segments"][0][0])
    return _rmsnorm(x, params["final_norm"]["scale"], c["norm_eps"])


def ref_logits(c, params, tokens, nm: Numerics):
    x = ref_hidden(c, params, tokens, nm)
    return nm.linear(x, params["embed"]["emb"]).astype(jnp.float32)


def ref_nll_sum(c, params, tokens, labels, nm: Numerics):
    logits = ref_logits(c, params, tokens, nm).astype(nm.dtype)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


def reference_train(c: dict, w: dict, params0, batches, nm: Numerics, steps: int = 3):
    """The first ``steps`` training steps, plainly, in blocks of
    ``reference_rows`` sequences; see ``models.fcn.reference_train``."""
    hp = c["optimizer"]
    rows = w["reference_rows"]
    grad_block = jax.jit(jax.value_and_grad(
        lambda p, t, y: ref_nll_sum(c, p, t, y, nm)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    p = nm.cast(params0)
    p_start = p
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for s in range(steps):
        toks, labels = batches[s]["tokens"], batches[s]["labels"]
        B, n_tok = toks.shape[0], toks.size
        total, grads = None, None
        for lo in range(0, B, rows):
            nll, g = grad_block(p, jnp.asarray(toks[lo:lo + rows]),
                                jnp.asarray(labels[lo:lo + rows]))
            total = nll if total is None else total + nll
            grads = g if grads is None else add(grads, g)
        grads = jax.tree.map(lambda g: (g / n_tok).astype(g.dtype), grads)
        losses.append(float(total / n_tok))
        grads = clip_by_global_norm(grads, hp["max_grad_norm"])
        if s == 0:
            g1 = np.asarray(leaf_norms(grads))
        p, m, v = adamw(grads, m, v, p, s + 1, learning_rate(hp, s), hp)
    change = np.asarray(leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                                - b.astype(jnp.float32), p, p_start)))
    return losses, g1, change


def synthetic_data(c: dict, w: dict, seed: int):
    """The repository's ``SyntheticLM`` token stream for this cell's seed."""
    from repro.data import DataConfig, SyntheticLM

    from bench.traffic import sub_seed

    return SyntheticLM(DataConfig(c["vocab"], w["seq"], w["batch"],
                                  seed=sub_seed(seed, "data")))


def reference_params(c: dict, seed: int):
    """The weights as the program got them, made again from the seed, in
    float32."""
    from bench.traffic import sub_seed

    p0 = init_params(c, jax.random.PRNGKey(sub_seed(seed, "weights")), jnp.dtype(c["dtype"]))
    return jax.tree.map(lambda x: x.astype(jnp.float32), p0)


def reference_inputs(c: dict, w: dict, seed: int):
    """The reference's weights and the first three batches."""
    data = synthetic_data(c, w, seed)
    return reference_params(c, seed), [data.batch(i) for i in range(3)]


# -- the program: training ---------------------------------------------------------


class TrainProgram:
    """The repository's LM training step (``launch.steps.make_train_step``
    under the configured policy) with its state, compiled once; batches come
    from the repository's ``SyntheticLM`` pipeline on the host."""

    def __init__(self, c: dict, w: dict, seed: int, fault=None):
        from repro.core import policy_from_spec
        from repro.launch.steps import TrainStepConfig, make_train_step
        from repro.optim import adamw_init

        from bench.traffic import sub_seed

        self.c, self.w, self.seed = c, w, seed
        hp = c["optimizer"]
        if hp["schedule"] != "warmup_cosine" or hp["floor"] != 0.1:
            raise ValueError("the LM step schedules warmup_cosine with floor 0.1")
        a = arch(c)
        step_fn = make_train_step(a, TrainStepConfig(
            lr=hp["lr"], warmup=hp["warmup"], total_steps=hp["total_steps"],
            max_grad_norm=hp["max_grad_norm"], weight_decay=hp["weight_decay"],
        ), policy=policy_from_spec(w["policy"]))
        self.dtype = jnp.dtype(c["dtype"])
        self._pkey = jax.random.PRNGKey(sub_seed(seed, "weights"))
        params = init_params(c, self._pkey, self.dtype)
        check_layout(c, params)
        self.data = synthetic_data(c, w, seed)
        self.kept = [self.data.batch(i) for i in range(3)]
        state = {"params": params, "opt": jax.jit(adamw_init)(params),
                 "step": jnp.zeros((), jnp.int32)}
        step_fn = _faulty(step_fn, fault)
        self.compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
            state, jax.tree.map(jnp.asarray, self.kept[0])).compile()
        self.state = state
        self._norms = jax.jit(leaf_norms)
        self._change = jax.jit(lambda p, p0: leaf_norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p, p0)))
        self._next = None

    def hlo_text(self) -> str:
        return self.compiled.as_text()

    def host_batch(self, i: int):
        return self.kept[i] if i < 3 else self.data.batch(i)

    def step(self, i: int):
        with span("train.batch"):
            b = jax.device_put(self.host_batch(i))
        self.state, metrics = self.compiled(self.state, b)
        return metrics["loss"]

    def first_grad_norms(self):
        return self._norms(self.state["opt"]["m"]) / (1.0 - self.c["optimizer"]["b1"])

    def change_norms(self):
        p0 = init_params(self.c, self._pkey, self.dtype)
        return self._change(self.state["params"], p0)

    def reference_inputs(self):
        return reference_inputs(self.c, self.w, self.seed)

    def free(self):
        self.state = None
        self.compiled = None


def _faulty(step_fn, fault):
    """Test-only breakage of the timed step (never set by the command line)."""
    if fault is None:
        return step_fn
    if fault == "state_unchanged":
        def f(state, batch):
            _, metrics = step_fn(state, batch)
            return state, metrics
        return f
    if fault == "half_batch":
        def f(state, batch):
            return step_fn(state, jax.tree.map(lambda a: a[: a.shape[0] // 2], batch))
        return f
    raise ValueError(f"unknown fault {fault!r}")


# -- the program: serving ----------------------------------------------------------


class ServeProgram:
    """The repository's continuous-batching ``ServeEngine`` over seeded
    weights: one request class under the configured policy, the cell's
    slots, cache extent and bucket grid, warmed up before traffic."""

    def __init__(self, c: dict, w: dict, seed: int, fault=None):
        from repro.core import policy_from_spec
        from repro.serving import BucketSpec, ServeEngine

        from bench.traffic import sub_seed

        e = w["engine"]
        self.c, self.seed = c, seed
        self.dtype = jnp.dtype(c["dtype"])
        self._pkey = jax.random.PRNGKey(sub_seed(seed, "weights"))
        params = init_params(c, self._pkey, self.dtype)
        check_layout(c, params)
        self.engine = ServeEngine(
            arch(c), params, n_slots=e["slots"], max_seq=e["max_seq"],
            policies={"interactive": policy_from_spec(w["policy"])},
            bucket_spec=BucketSpec(tuple(e["batch_buckets"]), e["len_step"],
                                   e["max_prompt"]),
        )
        if fault is not None:
            _break_tokens(self.engine, fault, c["vocab"])
        self.engine.warmup()
        self.slots = e["slots"]
        self.max_seq = e["max_seq"]

    def reference_params(self):
        return reference_params(self.c, self.seed)

    def free(self):
        self.engine = None


def _break_tokens(engine, fault, vocab):
    """Test-only: every decoded token altered where it is produced."""
    if fault != "token_altered":
        raise ValueError(f"unknown fault {fault!r}")
    for cls, fn in list(engine._decode_steps.items()):
        def bad(*args, _fn=fn):
            tok, segments = _fn(*args)
            return (tok + 1) % vocab, segments
        engine._decode_steps[cls] = bad


def served_gaps(c: dict, params, requests, max_seq: int, control=None):
    """For each served token of each ``(prompt, generated)`` request, the
    gap by which its logit lies below the reference's best at that
    position.  With ``control`` (a ``Numerics``), the gap of the token the
    control would put first at the same positions instead.  Returns the
    gaps of all tokens, concatenated."""
    f32 = Numerics("f32")

    @jax.jit
    def at_positions(params, tokens, served):
        ref = ref_logits(c, params, tokens, f32)[0]
        best = jnp.max(ref, axis=-1)
        if control is not None:
            served = jnp.argmax(ref_logits(c, params, tokens, control)[0], axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]

    out = []
    for prompt, generated in requests:
        seq = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
        P, n = len(prompt), len(generated)
        tokens = np.zeros((1, max_seq), np.int32)
        tokens[0, : len(seq)] = seq
        served = np.zeros(max_seq, np.int32)
        served[P - 1: P - 1 + n] = generated
        gaps = np.asarray(at_positions(params, jnp.asarray(tokens), jnp.asarray(served)))
        out.append(gaps[P - 1: P - 1 + n])
    return np.concatenate(out)
