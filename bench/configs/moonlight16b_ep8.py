"""Plain reference for ``moonlight16b_ep8``: Moonlight-16B-A3B
(DeepSeek-V3 layer equations: multi-head latent attention, sigmoid top-6
routing with a score-correction bias, 2 shared experts) as one chip's
share of an EP8 deployment, fine-tuned by trajectory balance on 64-token
continuations of a seeded prompt.

Written from the descriptions, in ``jax.numpy``, with no cache, no kernel
and no batching tricks; it imports nothing of the program.

- Prompt: ``prompt_len`` ids by ``numpy.random.RandomState(seed)
  .randint(0, vocab, prompt_len)``.  A trajectory appends ``length`` ids;
  the backward policy pops the last one (log P_B = 0).
- Reward: log R = beta * sum <U[x_t], V[x_t+1]> / sqrt(rank) over the
  continuation's pairs and its pair with the prompt's last id; U then V,
  (vocab, rank) standard normals by ``RandomState(seed + 1)``.
- Policy, per layer: x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x)).  MLA:
  q = x W_q (heads x (nope + rope)); [c, k_pe] = x W_kv_a; c = RMSNorm(c);
  [k_nope, v] = c W_kv_b; RoPE (rotate-half, theta) on q_pe and the
  shared k_pe; causal softmax at scale (nope + rope)^-1/2; W_o.  FFN:
  layer 0 a SiLU MLP (gate, up, down); later layers the routed experts
  this chip holds (experts 0..G-1 of ``n_routed_experts``; the router
  picks the top ``num_experts_per_tok`` of sigmoid(x W_r) + bias, weights
  the sigmoid scores renormalised, times ``routed_scaling_factor``; every
  held expert is applied to every token and weighted, zero where not
  chosen) plus the shared experts as one SiLU MLP.  Final RMSNorm, untied
  head over the vocabulary slice.

Fitting it beside the program: ``bench/refops.train_three_steps`` keeps
the parameters, both Adam moments, the first gradient and the next step's
trees at once, 32 bytes a parameter (18 GB at 568.5 M), more than the
chip holds.  So ``loss_fn`` places those trees in host memory, and each
loss-and-gradient call is computed on the chip in blocks of ``BLOCK``
trajectories, through a host callback, at ``highest`` matmul precision;
the gradient stays on the chip until the backward pass asks for it.
``train_three_steps`` makes its trees from numpy on JAX's default device,
after ``loss_fn`` has returned and outside this module, so no ``with``
block can hold them: ``loss_fn`` makes the host CPU the default device,
and ``param_shapes``, which every set-up asks first, gives the earlier
default back (``bench/control.py`` sets up several seeds' programs in one
process).
"""
from __future__ import annotations

import ctypes

import jax
import jax.numpy as jnp
import numpy as np

#: trajectories per block of the on-chip loss and gradient
BLOCK = 1


def dims(cfg):
    return cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]


def prompt(cfg):
    e = cfg["recipe_env"]
    return np.random.RandomState(e["seed"]).randint(
        0, e["vocab"], size=e["prompt_len"]).astype(np.int32)


def tables(cfg):
    e = cfg["recipe_env"]
    rs = np.random.RandomState(e["seed"] + 1)
    u = rs.standard_normal((e["vocab"], e["rank"])).astype(np.float32)
    v = rs.standard_normal((e["vocab"], e["rank"])).astype(np.float32)
    return u, v


def num_actions(cfg):
    return cfg["vocab_size"]


def param_shapes(cfg) -> dict:
    _restore_default_device()
    D, H, V = dims(cfg)
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, G, F = cfg["kv_lora_rank"], cfg["experts_held"], \
        cfg["moe_intermediate_size"]
    mlp = lambda f: {"gate": {"w": (D, f)}, "up": {"w": (D, f)},
                     "down": {"w": (f, D)}}
    layers = {}
    for i in range(cfg["num_hidden_layers"]):
        lp = {"attn_norm": {"scale": (D,)},
              "attn": {"q": {"w": (D, H * (nope + rope))},
                       "kv_a": {"w": (D, rank + rope)},
                       "kv_norm": {"scale": (rank,)},
                       "kv_b": {"w": (rank, H * (nope + vd))},
                       "o": {"w": (H * vd, D)}},
              "ffn_norm": {"scale": (D,)}}
        if i < cfg["first_k_dense_replace"]:
            lp["ffn"] = mlp(cfg["intermediate_size"])
        else:
            E = cfg["n_routed_experts"]
            lp["moe"] = {"router": {"w": (D, E), "bias": (E,)},
                         "experts": {"gate": {"w": (D, G, F)},
                                     "up": {"w": (D, G, F)},
                                     "down": {"w": (F, G, D)}},
                         "shared": mlp(cfg["n_shared_experts"] * F)}
        layers[f"layer_{i}"] = lp
    return {"embed": {"table": (V, D)}, "layers": layers,
            "final_norm": {"scale": (D,)}, "head": {"w": (D, V)},
            "log_z": ()}


# -- the model ----------------------------------------------------------------

def rms(scale, x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (S, ..., r) rotated by positions ``pos`` (S,), rotate-half."""
    r = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = (pos.astype(jnp.float32)[:, None] * inv).astype(x.dtype)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def silu_mlp(p, x):
    return (jax.nn.silu(x @ p["gate"]["w"]) * (x @ p["up"]["w"])) \
        @ p["down"]["w"]


def attention(cfg, p, x):
    S = x.shape[0]
    H, nope, rank = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["kv_lora_rank"])
    ropd, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    pos = jnp.arange(S)
    q = (x @ p["q"]["w"]).reshape(S, H, -1)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv_a = x @ p["kv_a"]["w"]
    c = rms(p["kv_norm"]["scale"], kv_a[:, :rank], cfg["rms_norm_eps"])
    k_pe = rope(kv_a[:, rank:], pos, theta)
    kv = (c @ p["kv_b"]["w"]).reshape(S, H, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (S, H, ropd))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(nope + ropd, x.dtype))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", a, kv[..., nope:]).reshape(S, -1) \
        @ p["o"]["w"]


def moe(cfg, p, x):
    s = jax.nn.sigmoid(x @ p["router"]["w"])
    _, idx = jax.lax.top_k(s + p["router"]["bias"], cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=s.dtype), 1) > 0
    w = jnp.where(chosen, s, 0)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]
    e = p["experts"]
    out = silu_mlp(p["shared"], x)
    for g in range(cfg["experts_held"]):
        mlp = {n: {"w": e[n]["w"][:, g]} for n in ("gate", "up", "down")}
        out = out + w[:, g, None] * silu_mlp(mlp, x)
    return out


def forward(cfg, params, tokens):
    """Logits (S, V) of one sequence (S,)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["table"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        lp = params["layers"][f"layer_{i}"]
        x = x + attention(cfg, lp["attn"], rms(lp["attn_norm"]["scale"], x,
                                               eps))
        h = rms(lp["ffn_norm"]["scale"], x, eps)
        x = x + (silu_mlp(lp["ffn"], h) if "ffn" in lp
                 else moe(cfg, lp["moe"], h))
    return rms(params["final_norm"]["scale"], x, eps) @ params["head"]["w"]


def log_reward(cfg, conts, dt=jnp.float32):
    e = cfg["recipe_env"]
    u, v = tables(cfg)
    first = np.full((conts.shape[0], 1), prompt(cfg)[-1], np.int32)
    seq = np.concatenate([first, np.asarray(conts, np.int32)], 1)
    score = jnp.sum(jnp.asarray(u[seq[:, :-1]], dt)
                    * jnp.asarray(v[seq[:, 1:]], dt), axis=(1, 2))
    return jnp.asarray(e["beta"], dt) * score / jnp.sqrt(
        jnp.asarray(e["rank"], dt))


# -- training -----------------------------------------------------------------

def train_batch(cfg, out):
    """What the reference needs of one program step's batch: its actions
    (T, B), which are the continuations' ids; ids outside the slice are
    illegal."""
    actions = np.asarray(out["actions"], np.int32)
    illegal = int(((actions < 0) | (actions >= cfg["vocab_size"])).sum())
    return {"actions": actions,
            "conts": np.clip(actions.T, 0, cfg["vocab_size"] - 1),
            "illegal": illegal}


def _release_host_memory():
    """Give the heap that the program's compile and steps freed back to
    the system (glibc ``malloc_trim``; on a one-chip TPU v5e host of 40
    GiB the compile left ~15 GB of it), and serve every later host
    allocation of 1 MiB or more by its own mapping
    (``mallopt(M_MMAP_THRESHOLD)``), so the trees the follower frees each
    step are returned too; the threshold holds for the rest of the
    process.  Without glibc, nothing changes."""
    try:
        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-3, 1 << 20)                  # M_MMAP_THRESHOLD
        libc.malloc_trim(0)
    except (OSError, AttributeError):
        pass


#: JAX's default device before ``loss_fn`` made it the host CPU
_default_before = []


def _host_default_device(cpu):
    if not _default_before:
        _default_before.append(jax.config.jax_default_device)
    jax.config.update("jax_default_device", cpu)


def _restore_default_device():
    if _default_before:
        jax.config.update("jax_default_device", _default_before.pop())


def _block_fn(cfg, dt):
    """Loss-and-gradient of one block on the chip: sum over its rows of
    weight x (log Z + sum_t log P_F - log R)^2, with the per-step log-probs
    and log-rewards."""
    pr = jnp.asarray(prompt(cfg))
    P = pr.shape[0]

    def block(params, conts, log_r, weight):
        lps = []
        for i in range(conts.shape[0]):
            logits = forward(cfg, params, jnp.concatenate([pr, conts[i]]))
            T = conts.shape[1]
            lp = jax.nn.log_softmax(logits[P - 1:P + T - 1], axis=-1)
            lps.append(jnp.take_along_axis(lp, conts[i][:, None], -1)[:, 0])
        log_pf = jnp.stack(lps, 1)                            # (T, b)
        delta = params["log_z"] + jnp.sum(log_pf, 0) - log_r
        loss = jnp.sum(weight * jnp.square(delta).astype(jnp.float32))
        return loss, log_pf

    return jax.jit(jax.value_and_grad(block, has_aux=True))


def loss_fn(cfg, dt, keep=None):
    """TB loss of a batch under params, every step computed in ``dt``;
    aux: per-step forward log-probs (T, B) and log-rewards (B,).  ``keep``
    limits the loss to the first ``keep`` trajectories (the fault 'half
    the batch left out').  See the module's note on where it runs: the
    forward callback computes the loss and keeps its gradient on the chip,
    the backward callback brings the gradient back."""
    _release_host_memory()
    cpu = jax.devices("cpu")[0]
    chip = jax.devices()[0]
    _host_default_device(cpu)
    block = _block_fn(cfg, dt)
    held = {}

    def forward_on_chip(params, conts):
        _release_host_memory()
        B = conts.shape[0]
        n = B if keep is None else keep
        weight = (np.arange(B) < n).astype(np.float32) / n
        log_r = np.asarray(jnp.asarray(log_reward(cfg, conts, jnp.float32),
                                       dt), np.float32)
        with jax.default_matmul_precision("highest"):
            p = jax.device_put(params, chip)
            loss, grads, lps = 0.0, None, []
            for s in range(0, B, BLOCK):
                sl = slice(s, s + BLOCK)
                (l, lp), g = block(p, jax.device_put(conts[sl], chip),
                                   jax.device_put(log_r[sl].astype(dt), chip),
                                   jax.device_put(weight[sl], chip))
                loss += float(l)
                lps.append(np.asarray(lp, np.float32))
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
        held["grads"] = grads
        return np.float32(loss), np.concatenate(lps, 1), log_r

    def backward_from_chip(ct, loss):
        grads = held.pop("grads")
        return jax.tree_util.tree_map(lambda g: np.asarray(g * float(ct)),
                                      grads)

    def forward(params, conts):
        T, B = conts.shape[1], conts.shape[0]
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
        return jax.pure_callback(forward_on_chip, (f32(), f32(T, B), f32(B)),
                                 params, conts)

    @jax.custom_vjp
    def tb(params, conts):
        loss, log_pf, log_r = forward(params, conts)
        return loss, {"log_pf": log_pf, "log_r": log_r}

    def tb_fwd(params, conts):
        loss, log_pf, log_r = forward(params, conts)
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        return (loss, {"log_pf": log_pf, "log_r": log_r}), (shapes, conts,
                                                             loss)

    def tb_bwd(res, ct):
        shapes, conts, loss = res
        # ``loss`` orders the callback after the forward one
        grads = jax.pure_callback(backward_from_chip, shapes, ct[0], loss)
        return grads, np.zeros(conts.shape, jax.dtypes.float0)

    tb.defvjp(tb_fwd, tb_bwd)

    def fn(params, batch):
        return tb(params, batch["conts"])

    return fn
