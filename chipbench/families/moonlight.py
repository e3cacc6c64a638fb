"""DeepSeek-V3-style MoE decoder (Moonlight-16B-A3B): its configuration
keys, its seeded leaves, its float32 equations and its operation counts.

The stack is ``first_k_dense_replace`` leading dense layers, then MoE
layers. Every layer is pre-norm attention then a pre-norm feed-forward,
each added to the residual; RMS norms scale by ``1 + scale`` with the
source's ``rms_norm_eps``.

Attention is multi-head latent attention without a query low-rank: q =
x wq gives each head ``qk_nope_head_dim`` + ``qk_rope_head_dim``
columns; the latent x wkva gives ``kv_lora_rank`` columns, RMS-normed
and up-projected by wkvb to each head's nope key and value, and
``qk_rope_head_dim`` rotary key columns that every head shares. Rotary
(halves rotated, theta from the configuration) turns the rope columns of
q and k; scores scale by (nope + rope) ** -0.5, causal, softmax in
float32.

The leading layers' feed-forward is a SwiGLU MLP of
``intermediate_size`` (its input matrix holds the gate's columns, then
the up projection's). An MoE layer routes over all the router's
``published`` experts: sigmoid scores of x @ router, the top
``num_experts_per_tok`` of the scores plus the selection bias, gates the
chosen scores normalised to sum 1 and times ``routed_scaling_factor``.
This chip holds ``n_routed_experts`` of them from ``first_held_expert``
on, and adds only their part: each token's sum over the chosen held
experts of gate times a SwiGLU MLP of ``moe_intermediate_size``. The
``n_shared_experts`` shared experts are one SwiGLU MLP of that many
times the expert width, on every token. Each MoE layer's balance loss is
DeepSeek-V3's sequence-wise sum over all the router's experts of f_i P_i:
f_i = E / (K S) times the copies sent to expert i, P_i the mean over the
sequence of its score over the sum of the scores. The training loss is
the cross-entropy plus 0.01 times the sum of the layers' balance losses;
the loss compared is the cross-entropy.

Every held expert runs on every token and its output is weighed by the
token's gate for it (zero when not chosen): plain, and for the int4
control the same as computing the routed rows alone, since int4 rounds
each row of activations on its own. Under ``bits=4`` every matrix
product's operands go through ``reference.mm``'s int4 control; the
router stays float32.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference import HIGHEST, mm, rms, rope, row_mean_grads

AUX_COEF = 0.01


def _n_experts(conf) -> int:
    """The router's width: the published count of routed experts."""
    return conf.get("published", {}).get("n_routed_experts",
                                         conf["n_routed_experts"])


def arch_config(conf):
    """The repository's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig

    if conf["q_lora_rank"] is not None or conf["hidden_act"] != "silu" \
            or conf["scoring_func"] != "sigmoid" or conf["n_group"] != 1 \
            or not conf["norm_topk_prob"]:
        raise ValueError(f"{conf['name']}: not a shape this family states")
    return ArchConfig(
        name=conf["name"], family="moe", unit=("moe",), norm="rms",
        dtype="bfloat16", act="swiglu",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], head_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        norm_eps=conf["rms_norm_eps"],
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_dim=conf["qk_nope_head_dim"],
        qk_rope_dim=conf["qk_rope_head_dim"],
        n_lead=conf["first_k_dense_replace"],
        n_experts=_n_experts(conf), top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        n_shared_experts=conf["n_shared_experts"],
        score_func="sigmoid",
        routed_scale=conf["routed_scaling_factor"],
        held_experts=conf["n_routed_experts"],
        first_expert=conf.get("first_held_expert", 0),
    )


def _attn_leaves(cfg, n, out_std):
    d, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.head_dim)
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "wq": ((n, d, H * (dn + dr)), bf, W.STD),
        "wkva": ((n, d, r + dr), bf, W.STD),
        "kv_norm/scale": ((n, r), f32, 0.0),
        "wkvb": ((n, r, H * (dn + dv)), bf, W.STD),
        "wo": ((n, H * dv, d), bf, out_std),
        "ln1/scale": ((n, d), f32, 0.0),
        "ln2/scale": ((n, d), f32, 0.0),
    }


def leaf_table(cfg):
    """``path -> (shape, dtype, std)`` of the params; std 0 marks a zero
    leaf (norm scales, the selection bias). ``blocks/lead/`` stacks the
    leading dense layers, ``blocks/moe/`` the MoE layers, whose expert
    leaves hold this chip's experts on their second axis. Paths follow
    the tree of ``repro.models.init_params``."""
    d, L, vp = cfg.d_model, cfg.n_layers, W.padded_vocab(cfg)
    n_moe = L - cfg.n_lead
    E, Hx, fe = cfg.n_experts, cfg.n_held, cfg.expert_d_ff
    fs = cfg.n_shared_experts * fe
    out_std = W.STD / max(1.0, (2 * L) ** 0.5)
    bf, f32 = jnp.bfloat16, jnp.float32
    table = {
        "embed": ((vp, d), bf, W.STD),
        "final_norm/scale": ((d,), f32, 0.0),
        "lm_head": ((d, vp), bf, W.STD),
    }
    lead = _attn_leaves(cfg, cfg.n_lead, out_std)
    lead.update({"mlp/wi": ((cfg.n_lead, d, 2 * cfg.d_ff), bf, W.STD),
                 "mlp/wo": ((cfg.n_lead, cfg.d_ff, d), bf, out_std)})
    moe = _attn_leaves(cfg, n_moe, out_std)
    moe.update({
        "moe/router": ((n_moe, d, E), f32, W.STD),
        "moe/bias": ((n_moe, E), f32, 0.0),
        "moe/w1": ((n_moe, Hx, d, 2 * fe), bf, W.STD),
        "moe/w2": ((n_moe, Hx, fe, d), bf, out_std),
        "moe/shared/wi": ((n_moe, d, 2 * fs), bf, W.STD),
        "moe/shared/wo": ((n_moe, fs, d), bf, out_std),
    })
    table.update({f"blocks/lead/{k}": v for k, v in lead.items()})
    table.update({f"blocks/moe/{k}": v for k, v in moe.items()})
    return table


# ------------------------------------------------------------- model --
def _swiglu(x, wi, wo, bits, quant_act):
    g, u = jnp.split(mm(x, wi, bits, quant_act), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, wo, bits, quant_act)


def _attention(cfg, p, x, bits, quant_act):
    """Latent attention over one sequence x (S, d), normed input."""
    S = x.shape[0]
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    dv, r = cfg.head_dim, cfg.kv_lora_rank
    pos = jnp.arange(S)
    q = mm(x, p["wq"], bits, quant_act).reshape(S, H, dn + dr)
    c, k_rope = jnp.split(mm(x, p["wkva"], bits, quant_act), [r], axis=-1)
    c = rms(c, p["kv_norm"], cfg.norm_eps)
    kv = mm(c, p["wkvb"], bits, quant_act).reshape(S, H, dn + dv)
    k_nope, v = jnp.split(kv, [dn], axis=-1)
    q = jnp.concatenate(
        [q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)], -1)
    k_rope = rope(k_rope[:, None, :], pos, cfg.rope_theta)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (S, H, dr))], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * (dn + dr) ** -0.5
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    return mm(o.reshape(S, H * dv), p["wo"], bits, quant_act)


def route(cfg, p, x):
    """(chosen experts (S, K), their gates (S, K), balance loss) of one
    sequence's normed input x (S, d), over all the router's experts."""
    E, K = cfg.n_experts, cfg.top_k
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"], precision=HIGHEST))
    _, idx = jax.lax.top_k(scores + jax.lax.stop_gradient(p["bias"]), K)
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg.routed_scale
    S = x.shape[0]
    f = jnp.sum(jax.nn.one_hot(idx, E), axis=(0, 1)) * (E / (K * S))
    P = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    return idx, gate, jnp.sum(f * P)


def moe(cfg, p, x, bits=0, quant_act=True):
    """(this chip's part of the MoE output, balance loss) for one
    sequence's normed input x (S, d): the held experts' gated sum and
    the shared experts."""
    idx, gate, aux = route(cfg, p, x)
    local = jnp.arange(cfg.n_held) + cfg.first_expert
    # (S, held): each token's gate for each held expert, 0 if not chosen.
    weight = jnp.sum(jnp.where(idx[:, :, None] == local, gate[:, :, None],
                               0.0), axis=1)
    ys = jax.vmap(lambda w1, w2: _swiglu(x, w1, w2, bits, quant_act))(
        p["w1"], p["w2"])  # (held, S, d)
    y = jnp.einsum("es,esd->sd", weight.T, ys, precision=HIGHEST)
    y = y + _swiglu(x, p["shared_wi"], p["shared_wo"], bits, quant_act)
    return y, aux


def block(cfg, p, x, bits=0, quant_act=True):
    """One layer over one sequence x (S, d): (x, balance loss, 0 for a
    dense layer). ``p`` is one layer's weights as ``stack`` gives them;
    an MoE layer's have a ``router``."""
    x = x + _attention(cfg, p, rms(x, p["ln1"], cfg.norm_eps), bits,
                       quant_act)
    xn = rms(x, p["ln2"], cfg.norm_eps)
    if "router" in p:
        y, aux = moe(cfg, p, xn, bits, quant_act)
    else:
        y, aux = _swiglu(xn, p["wi"], p["wo2"], bits, quant_act), 0.0
    return x + y, aux


def layer(cfg, p, x, bits=0, quant_act=True):
    """One layer of either type over one sequence x (S, d)."""
    return block(cfg, p, x, bits, quant_act)[0]


def _attn_weights(b):
    return {"wq": b["wq"], "wkva": b["wkva"], "kv_norm": b["kv_norm"]["scale"],
            "wkvb": b["wkvb"], "wo": b["wo"], "ln1": b["ln1"]["scale"],
            "ln2": b["ln2"]["scale"]}


def stack(params):
    """``block``'s weights from the program's tree, per group of the
    stack: ``{"lead": ..., "moe": ...}``, each layer-stacked."""
    b = params["blocks"]
    lead = dict(_attn_weights(b["lead"]), wi=b["lead"]["mlp"]["wi"],
                wo2=b["lead"]["mlp"]["wo"])
    m = b["moe"]["moe"]
    moe_w = dict(_attn_weights(b["moe"]), router=m["router"],
                 bias=m["bias"], w1=m["w1"], w2=m["w2"],
                 shared_wi=m["shared"]["wi"], shared_wo=m["shared"]["wo"])
    return {"lead": lead, "moe": moe_w}


def row_loss(cfg, params, tokens, labels, bits=0):
    """One sequence's cross-entropy plus ``AUX_COEF`` times its summed
    balance loss, whose value is left out: the value is the
    cross-entropy, the gradient that of the whole training loss."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(carry, p):
        x, aux = carry
        x, a = block(cfg, p, x, bits)
        return (x, aux + a), None

    aux = jnp.float32(0.0)
    for group in stack(params).values():
        (x, aux), _ = jax.lax.scan(body, (x, aux), group)
    x = rms(x, params["final_norm"]["scale"], cfg.norm_eps)
    logits = mm(x, params["lm_head"], bits)[:, : cfg.vocab]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    reg = AUX_COEF * aux
    return jnp.mean(lse - gold) + reg - jax.lax.stop_gradient(reg)


def batch_grads(cfg, params, tokens, labels, bits=0):
    """(cross-entropy, gradient of the training loss) of the batch, one
    row at a time: the balance loss is per sequence, so the batch's is
    the mean of the rows'."""
    return row_mean_grads(
        lambda p, t, l: row_loss(cfg, p, t, l, bits), params, tokens,
        labels)


# ------------------------------------------------------------ counts --
def attn_params(cfg) -> int:
    """Weights of one layer's attention products."""
    d, H = cfg.d_model, cfg.n_heads
    r, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.head_dim
    return (d * H * cfg.qk_head_dim + d * (r + dr)
            + r * H * (cfg.qk_nope_dim + dv) + H * dv * d)


def expert_params(cfg) -> int:
    """Weights of one routed expert (SwiGLU: three matrices)."""
    return 3 * cfg.d_model * cfg.expert_d_ff


def token_params(cfg) -> float:
    """Product weights one token uses in a step, on average: each
    layer's attention, the leading MLPs, the router and the shared
    experts, the expected share K * held / E of the routed experts, and
    the output head. The embedding is a lookup, not a product."""
    d = cfg.d_model
    lead = 3 * d * cfg.d_ff
    n_moe = cfg.n_layers - cfg.n_lead
    moe = (d * cfg.n_experts + cfg.n_shared_experts * expert_params(cfg)
           + cfg.top_k * cfg.n_held / cfg.n_experts * expert_params(cfg))
    return (cfg.n_layers * attn_params(cfg) + cfg.n_lead * lead
            + n_moe * moe + d * cfg.vocab)


def causal_attention_flops(cfg, seq: int) -> float:
    """Forward operations of causal attention over one sequence, all
    layers: scores over the query width, weighted sum over the value
    width, the lower triangle."""
    return (cfg.n_layers * seq * seq * cfg.n_heads
            * (cfg.qk_head_dim + cfg.head_dim))


def train_step_flops(cfg, batch: int, seq: int) -> float:
    """Forward and backward operations of one step (3x forward);
    recomputation for remat is not counted."""
    fwd = 2.0 * token_params(cfg) * batch * seq \
        + batch * causal_attention_flops(cfg, seq)
    return 3.0 * fwd


def expected_group_rows(cfg, tokens: int, align: int = 128) -> float:
    """Expected live rows of one MoE layer's expert buffer in a step: the
    token-copies routed to the held experts under even routing, tokens *
    K * held / E, and each held expert's group padded to a whole block,
    (align - 1) / 2 rows on average (``gam_group_quant_roofline``)."""
    return (tokens * cfg.top_k * cfg.n_held / cfg.n_experts
            + cfg.n_held * (align - 1) / 2)


def decode_step_flops(cfg, cache_lens: Sequence[int]) -> float:
    raise NotImplementedError(
        "the program does not serve latent attention (training only)")
