"""A decoder whose layers differ in kind — the description serving reads.

:class:`HybridConfig` says, layer by layer, which **mixer** a block has
(``"kda"``: Kimi Delta Attention, a linear-attention layer with a
per-sequence recurrent state; ``"mla"``: multi-head latent attention, whose
cache is one latent row a token; ``"ssm_gqa"``: Falcon-H1's parallel block,
a Mamba-2 state-space branch with a per-sequence state and a grouped-query
attention branch over K/V pages, both reading one norm and both added to
the residual) and which **FFN** (``"dense"``: SwiGLU;
``"moe"``: a routed SwiGLU layer plus a shared expert), over RMSNorm
pre-norm blocks, an untied head and partial rotary on MLA's rope
dimensions.  It stands beside :class:`apex_tpu.models.gpt.GptConfig`:
``apex_tpu.serve`` takes either, and runs both through one block
(``serve/model.py::_block``).

The per-layer pattern is the configuration's DATA: ``pattern`` is a tuple
of ``(mixer, ffn)``, one a layer, built by whoever constructs the config.
``layer_group_size`` / ``first_dense_layers`` stay as a way to *state* the
Ling family's pattern (:func:`ling_pattern`), used when ``pattern`` is not
given.

The routed FFN is described as ONE CHIP'S SHARE of an expert-parallel
group: ``num_experts`` is what the router scores, ``held_experts = (first,
count)`` the experts whose weights this chip holds and whose terms it adds;
``vocab_size`` is likewise the vocabulary slice held.  Nothing stands in
for the other chips.

Only serving runs this stack: there is no flax module and no training
path; :func:`param_shapes` / :func:`init_params` give the parameter tree
the serving bodies read (a plain dict; ``params["params"]["layers"]`` is a
list, one dict a layer, since the layers differ).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["HybridConfig", "ling_pattern", "param_shapes", "init_params",
           "leaf_rule", "draw_leaf", "path_names"]

KDA, MLA, SSM_GQA = "kda", "mla", "ssm_gqa"
DENSE, MOE = "dense", "moe"
#: the mixer kinds that keep a per-sequence recurrent state
RECURRENT = (KDA, SSM_GQA)


def ling_pattern(num_layers: int, layer_group_size: int,
                 first_dense_layers: int, routed: bool):
    """The Ling family's pattern: layer ``i`` is MLA when ``(i + 1) %
    layer_group_size == 0``, else KDA; the first ``first_dense_layers``
    layers (every layer of a model with no experts) keep a dense FFN."""
    return tuple(
        (
            MLA if (i + 1) % layer_group_size == 0 else KDA,
            DENSE if i < first_dense_layers or not routed else MOE,
        )
        for i in range(num_layers)
    )


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int                 # the vocabulary slice held
    hidden_size: int
    num_layers: int
    num_heads: int
    head_dim: int                   # KDA key/value width; GQA head width
    intermediate_size: int          # dense SwiGLU width
    max_seq_len: int
    #: ``((mixer, ffn), ...)``, one a layer; None: the Ling pattern stated
    #: by the next two fields (:func:`ling_pattern`)
    pattern: Optional[Tuple[Tuple[str, str], ...]] = None
    #: layer i is MLA when (i + 1) % layer_group_size == 0, else KDA
    layer_group_size: int = 6
    #: the first layers keep a dense FFN
    first_dense_layers: int = 0
    #: K/V heads of a grouped-query attention branch (0: ``num_heads``)
    num_kv_heads: int = 0
    # -- routed FFN ------------------------------------------------------
    num_experts: int = 0            # experts the router scores (0: none)
    held_experts: Tuple[int, int] = (0, 0)   # (first, count) held here
    moe_intermediate_size: int = 0
    shared_intermediate_size: int = 0
    top_k: int = 8
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # -- MLA ---------------------------------------------------------------
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    # -- KDA ---------------------------------------------------------------
    conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    rms_eps: float = 1e-6
    # -- Mamba-2 branch of "ssm_gqa" ---------------------------------------
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_chunk: int = 128
    # -- muP multipliers (Falcon-H1; 1 elsewhere) --------------------------
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    #: over in_proj's z | x | B | C | dt channels
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5
    #: on the SwiGLU gate's pre-activation, on the FFN's output
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    dtype: Any = jnp.bfloat16       # compute dtype
    param_dtype: Any = jnp.bfloat16
    # what the engine asks of every model description
    sequence_parallel: bool = False
    context_parallel: Optional[str] = None

    def __post_init__(self):
        first, count = self.held_experts
        if self.num_experts and not (
            0 <= first and count > 0 and first + count <= self.num_experts
        ):
            raise ValueError(
                f"held_experts {self.held_experts} is not a run of the "
                f"{self.num_experts} experts"
            )
        if self.num_experts and self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        pattern = self.pattern
        if pattern is None:
            pattern = ling_pattern(
                self.num_layers, self.layer_group_size,
                self.first_dense_layers, bool(self.num_experts))
        pattern = tuple((str(m), str(f)) for m, f in pattern)
        if len(pattern) != self.num_layers:
            raise ValueError(
                f"pattern names {len(pattern)} layers, num_layers is "
                f"{self.num_layers}")
        for mixer, ffn in pattern:
            if mixer not in (KDA, MLA, SSM_GQA) or ffn not in (DENSE, MOE):
                raise ValueError(f"unknown layer kind {(mixer, ffn)}")
        object.__setattr__(self, "pattern", pattern)
        if any(m == SSM_GQA for m, _ in pattern):
            kv = self.kv_heads
            if self.num_heads % kv:
                raise ValueError("num_kv_heads must divide num_heads")
            if not (self.ssm_heads and self.ssm_head_dim and self.ssm_state):
                raise ValueError(
                    "an ssm_gqa layer needs ssm_heads, ssm_head_dim and "
                    "ssm_state")
            if self.ssm_heads % self.ssm_groups:
                raise ValueError("ssm_groups must divide ssm_heads")

    @property
    def kinds(self) -> Tuple[Tuple[str, str], ...]:
        """``((mixer, ffn), ...)``, one a layer."""
        return self.pattern

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def ssm_width(self) -> int:
        """``d_ssm``: the state-space branch's inner width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels the short convolution runs over: ``x | B | C``."""
        return self.ssm_width + 2 * self.ssm_groups * self.ssm_state

    def layers_of(self, mixer: str) -> Tuple[int, ...]:
        return tuple(i for i, k in enumerate(self.kinds) if k[0] == mixer)

    @property
    def stateful(self) -> bool:
        """Some layer keeps a per-sequence recurrent state."""
        return any(k[0] in RECURRENT for k in self.kinds)

    @property
    def routed(self) -> bool:
        return any(k[1] == MOE for k in self.kinds)


def _mat(cfg, *shape):
    return jax.ShapeDtypeStruct(shape, cfg.param_dtype)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _layer_shapes(cfg: HybridConfig, mixer: str, ffn: str) -> dict:
    h, n, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    lp = {"norm_mixer": {"scale": _f32(h)}, "norm_ffn": {"scale": _f32(h)}}
    if mixer == KDA:
        lp["kda"] = {
            # q | k | v | decay gate, one matmul
            "qkvg": {"weight": _mat(cfg, h, 4 * n * d)},
            "g_bias": _f32(n * d),
            "conv": _f32(cfg.conv_kernel, 3 * n * d),
            "beta": {"weight": _mat(cfg, h, n)},
            "ogate": {"weight": _mat(cfg, h, n)},
            "o_norm": {"scale": _f32(d)},
            "out": {"weight": _mat(cfg, n * d, h)},
        }
    elif mixer == SSM_GQA:
        ds, c, nh = cfg.ssm_width, cfg.ssm_conv_width, cfg.ssm_heads
        lp["ssm"] = {
            # z | x B C | dt, one matmul
            "in_proj": {"weight": _mat(cfg, h, ds + c + nh)},
            "conv": _f32(cfg.conv_kernel, c),
            "conv_bias": _f32(c),
            "dt_bias": _f32(nh),
            "a_log": _f32(nh),
            "d": _f32(nh),
            "norm": {"scale": _f32(ds)},
            "out_proj": {"weight": _mat(cfg, ds, h)},
        }
        lp["attn"] = {
            # q | k | v, one matmul
            "wqkv": {"weight": _mat(cfg, h, (n + 2 * cfg.kv_heads) * d)},
            "wo": {"weight": _mat(cfg, n * d, h)},
        }
    else:
        dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim, cfg.kv_lora_rank)
        lp["mla"] = {
            "q": {"weight": _mat(cfg, h, n * (dn + dr))},
            "kv_a": {"weight": _mat(cfg, h, r + dr)},
            "kv_norm": {"scale": _f32(r)},
            "kv_b": {"weight": _mat(cfg, r, n * (dn + dv))},
            "ogate": {"weight": _mat(cfg, h, n)},
            "out": {"weight": _mat(cfg, n * dv, h)},
        }
    if ffn == DENSE:
        i = cfg.intermediate_size
        lp["mlp"] = {"gate": {"weight": _mat(cfg, h, i)},
                     "up": {"weight": _mat(cfg, h, i)},
                     "down": {"weight": _mat(cfg, i, h)}}
    else:
        e, im, si = (cfg.held_experts[1], cfg.moe_intermediate_size,
                     cfg.shared_intermediate_size)
        lp["moe"] = {
            # the router and its scores stay f32
            "router": {"weight": _f32(h, cfg.num_experts)},
            "expert_bias": _f32(cfg.num_experts),
            "experts": {"gate": _mat(cfg, e, h, im), "up": _mat(cfg, e, h, im),
                        "down": _mat(cfg, e, im, h)},
            "shared": {"gate": {"weight": _mat(cfg, h, si)},
                       "up": {"weight": _mat(cfg, h, si)},
                       "down": {"weight": _mat(cfg, si, h)}},
        }
    return lp


def param_shapes(cfg: HybridConfig) -> dict:
    """The parameter tree as ``ShapeDtypeStruct`` leaves."""
    h, v = cfg.hidden_size, cfg.vocab_size
    return {"params": {
        "word_embeddings": {"weight": _mat(cfg, v, h)},
        "lm_head": {"weight": _mat(cfg, h, v)},
        "norm_f": {"scale": _f32(h)},
        "layers": [_layer_shapes(cfg, *k) for k in cfg.kinds],
    }}


def leaf_rule(names: Tuple[str, ...], hidden: int):
    """``(law, a, b)`` of one leaf by its place in the tree: norm scales
    are ones; the expert bias zeros; the router N(0, 1/sqrt(hidden))
    (unit-spread logits over a unit-RMS input, so the top-k is no tie); the
    decay-gate bias U(-6, -2) (decays that remember tens to hundreds of
    tokens); the conv taps N(0, 0.5); every other matrix N(0, 0.02).  The
    state-space leaves follow Mamba-2's published init: ``a_log = log A``, A
    ~ U(1, 16); ``dt_bias`` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1]; ``d`` ones; the conv bias zeros."""
    name = names[-2] if names[-1] == "weight" else names[-1]
    if names[-1] == "scale" or name == "d":
        return "ones", 0.0, 0.0
    if name in ("expert_bias", "conv_bias"):
        return "zeros", 0.0, 0.0
    if name == "g_bias":
        return "uniform", -6.0, -2.0
    if name == "a_log":
        return "log_of_uniform", 1.0, 16.0
    if name == "dt_bias":
        return "inv_softplus_log_uniform", 1e-3, 1e-1
    return "normal", 0.0, {"router": hidden ** -0.5, "conv": 0.5}.get(
        name, 0.02)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def draw_leaf(law: str, shape, dtype, key, a, b):
    """One leaf in its own dtype (compiled once a law, shape and dtype)."""
    if law == "ones":
        return jnp.ones(shape, dtype)
    if law == "zeros":
        return jnp.zeros(shape, dtype)
    if law == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, a, b).astype(dtype)
    if law == "log_of_uniform":
        return jnp.log(
            jax.random.uniform(key, shape, jnp.float32, a, b)).astype(dtype)
    if law == "inv_softplus_log_uniform":
        dt = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, jnp.log(a), jnp.log(b)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (a + b * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def init_params(cfg: HybridConfig, seed: int = 0) -> dict:
    """Random parameters, drawn leaf by leaf in each leaf's own dtype (no
    f32 copy of a bf16 tree is ever held)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))
    base = jax.random.PRNGKey(seed)
    out = []
    for i, (path, s) in enumerate(leaves):
        law, a, b = leaf_rule(path_names(path), cfg.hidden_size)
        out.append(draw_leaf(
            law, s.shape, s.dtype, jax.random.fold_in(base, i), a, b))
    return jax.tree_util.tree_unflatten(treedef, out)
