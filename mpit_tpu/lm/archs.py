"""The table of blocks: what sizes each decoder takes, and how it is
made from them.  One entry a size (``SIZES``: its one name, its one
default, whose type is its type, and what it means) and one a block
(``BLOCKS``).  The launcher's ``lm_*`` switches (``train/launch.py``),
the trainer's config (``lm/trainer.py`` ``LM_DEFAULTS``) and
``lm/model.py`` ``build`` are derived from here; a new block is its
decoder (``models/transformer.py``) and its entry here.

The data is plain Python: this file imports neither jax nor flax, and a
decoder is imported when its maker is called (the gang's parent reads
the launcher's defaults and must load nothing for it).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, NamedTuple, Tuple


class Size(NamedTuple):
    default: Any  # its type is the size's type wherever it is taken
    doc: str


SIZES: Dict[str, Size] = {
    # every block's
    "d_model": Size(64, "the residual stream's width"),
    "n_heads": Size(4, "query heads"),
    "n_layers": Size(2, "the layers held here"),
    "seq_len": Size(128, "positions of a packed sequence; gpt2's position "
                    "table is exactly this long (the stream fills whole "
                    "sequences, and an exact fit keeps the table out of the "
                    "sharding slack); the others' positions are rotary.  It "
                    "counts tokens, not rows: sdar's layers see 2 x seq_len "
                    "rows, a noised and a clean copy of every sequence"),
    "vocab": Size(0, "rows of the token table and the head; 0: the byte "
                  "stream's 256, whose ids index a larger table's first rows"),
    # the sparse MLP's, and the dense one beside it
    "n_experts": Size(8, "the router's width"),
    "experts_per_tok": Size(2, "experts a token takes"),
    "expert_width": Size(32, "one expert's inner width"),
    "experts_first": Size(0, "the first expert of the share held here"),
    "experts_held": Size(0, "0: all n_experts, else the contiguous share from "
                         "experts_first that this chip holds of the router's"),
    "route_scale": Size(1.0, "the router's routed_scaling_factor"),
    "dense_layers": Size(0, "how many layers, the first, have the dense MLP "
                         "of dense_width and not the sparse one"),
    "dense_width": Size(0, "the dense MLP's inner width (ouro's one MLP; "
                        "granite's gated one beside every mixer)"),
    # rotary positions, RMSNorm, grouped heads
    "rope_theta": Size(10000.0, "the rotary base; kimi's 0: no rotary "
                       "embedding in its latent attention"),
    "norm_eps": Size(1e-5, "the RMSNorm epsilon"),
    "kv_heads": Size(0, "key/value heads; 0: as many as query heads"),
    "head_dim": Size(0, "a head's width; 0: d_model / n_heads"),
    # mellum's window and YaRN
    "window": Size(0, "the sliding window; 0: every layer full"),
    "full_every": Size(4, "every full_every-th layer is full"),
    "yarn_factor": Size(0.0, "YaRN on the full layers; 0: the plain rotary "
                        "table on them too"),
    "yarn_orig": Size(0, "YaRN's original_max_position_embeddings"),
    "yarn_beta_fast": Size(32.0, "YaRN's beta_fast"),
    "yarn_beta_slow": Size(1.0, "YaRN's beta_slow"),
    "yarn_attn_factor": Size(1.0, "YaRN's attention_factor"),
    # lfm2's mixers
    "layer_types": Size("", "each held layer's token mixer, comma-separated, "
                        "n_layers of them: lfm2's conv or full_attention, "
                        "kimi's kda or full_attention, trinity's "
                        "sliding_attention (window keys, rotary positions) "
                        "or full_attention (no positions), nemotron's "
                        "mamba, moe or attention: one branch a layer; "
                        "qwen3next's linear_attention or full_attention; "
                        "granite's mamba or attention, each before a "
                        "gated MLP"),
    "conv_kernel": Size(3, "the taps of a short causal depthwise convolution "
                        "(lfm2's gated one; kimi's on q, k and v: 4; "
                        "nemotron's on x, B and C together, with a bias: 4; "
                        "qwen3next's on q, k and v together, no bias: 4; "
                        "granite's as nemotron's: 4)"),
    # ouro's loop
    "loop_steps": Size(4, "how often the layers are applied, same weights"),
    "exit_beta": Size(0.1, "the exit distribution's entropy's weight in the "
                      "block's loss"),
    "exit_bias": Size(0.0, "what the exit gate's bias is seeded at (0: a gate "
                      "of a half; negative: nearer to running every pass)"),
    # joyai's latent attention, shared expert and second head
    "q_rank": Size(0, "the queries' low-rank product's inner width; kimi's "
                   "0: no query latent, one product"),
    "kv_rank": Size(0, "the keys' and values' low-rank product's inner width"),
    "qk_nope": Size(0, "a head's query and key without positions"),
    "qk_rope": Size(0, "the rotary part of a head's query and key (even)"),
    "v_head": Size(0, "a head's value width"),
    "shared_experts": Size(1, "experts of expert_width every token takes "
                           "beside the routed ones"),
    "mtp_layers": Size(1, "0 or 1: the multi-token-prediction module"),
    "mtp_weight": Size(0.3, "the MTP loss's weight in the block's objective"),
    # kimi's delta attention
    "kda_heads": Size(0, "heads of the delta attention's state"),
    "kda_head_dim": Size(0, "a state's side: a head's keys and values"),
    # keye's indexer: the learned selection of keys
    "index_heads": Size(0, "the indexer's query heads, over its one key "
                        "head"),
    "index_head_dim": Size(0, "an indexer head's width (even: all of it "
                           "is rotated)"),
    "index_topk": Size(0, "keys a query attends: the positions its "
                       "indexer scores highest, all of them where fewer "
                       "came before"),
    # sdar's block-diffusion pass
    "block_len": Size(4, "positions of a diffusion block: a noised block "
                      "attends itself both ways and the clean blocks before "
                      "it (a power of two up to 16 that divides seq_len)"),
    "mask_id": Size(-1, "the id a noised position carries; -1: the table's "
                    "last row"),
    "noise_seed": Size(0, "keys the noise with the row's own ids: which "
                       "positions of which blocks are masked"),
    # trinity's balancing rule and scaled input
    "bias_rate": Size(0.0, "the step of the rule that moves a router's "
                      "selection bias from each pass's own expert loads "
                      "(load_balance_coeff); over 0 the bias leaves are "
                      "the vector's plain ranges, which no optimizer "
                      "owns (models/flat.py plain_ranges); 0: no rule, "
                      "every other block's value"),
    "embed_scale": Size(1.0, "what the token table's rows are multiplied "
                        "by on their way into the stream (mup_enabled: "
                        "the square root of d_model)"),
    # nemotron's state-space mixer, its shared expert and its seeding
    "ssm_heads": Size(0, "heads of the state-space mixer's state"),
    "ssm_head_dim": Size(0, "a state-space head's width (the state's "
                         "rows)"),
    "ssm_groups": Size(1, "groups of heads that share B and C, and the "
                       "groups of the gated RMSNorm (divides ssm_heads)"),
    "ssm_state": Size(0, "the state's columns: B's and C's width a group"),
    "ssm_chunk": Size(128, "positions of a chunk of the state's scan"),
    "shared_width": Size(0, "the shared expert's own inner width; 0: "
                         "shared_experts x expert_width"),
    "init_depth": Size(0, "the depth whose square root divides the seeded "
                       "std of the mixers' output projections "
                       "(rescale_prenorm_residual: the published layer "
                       "count); 0: seeded as the rest"),
    # qwen3next's gated delta rule and its partly rotated heads
    "gdn_key_heads": Size(0, "key (and query) heads of the gated delta "
                          "rule's state"),
    "gdn_value_heads": Size(0, "its value heads, a multiple of the key "
                            "heads: key head j serves value heads r j .. "
                            "r j + r - 1"),
    "gdn_key_dim": Size(0, "a key head's width: the state's rows"),
    "gdn_value_dim": Size(0, "a value head's width: the state's columns"),
    "rotary_factor": Size(1.0, "the share of a head's width that is "
                          "rotated, from its first dimension on "
                          "(partial_rotary_factor); 1: the whole head"),
    # granite's multipliers (embed_scale is its embedding_multiplier)
    "residual_scale": Size(1.0, "what every branch is multiplied by before "
                           "it joins the stream (residual_multiplier)"),
    "attn_scale": Size(0.0, "what the attention's scores are multiplied "
                       "by in place of 1 / sqrt(head_dim) "
                       "(attention_multiplier); 0: that"),
    "logits_scale": Size(1.0, "what the logits are divided by "
                         "(logits_scaling)"),
}

DEFAULTS = {name: size.default for name, size in SIZES.items()}
#: the launcher's switch for a size is ``lm_<size>`` but for these
SWITCH_ALIASES = {"n_experts": "lm_experts", "n_heads": "lm_heads",
                  "n_layers": "lm_layers", "seq_len": "lm_seq"}
#: size -> its launcher switch
SWITCHES = {name: SWITCH_ALIASES.get(name, f"lm_{name}") for name in SIZES}

# the two loss conventions (``lm/model.py``): the head's next-token NLL
# closed over log-probs, or the block's own, ``module(inputs, targets) ->
# (loss, {name: device scalar})``.  A block's own need be no NLL of the
# next token: sdar's is the block-diffusion bound, the cross-entropy of
# masked positions with their own ids over a noised and a clean copy of
# the inputs (the targets are read by nothing), and ``seq_len`` counts
# the tokens of a sequence, not the rows its layers see
HEAD_NLL, OWN_LOSS = "head_nll", "own_loss"


#: positions of the initialisation's sample where a block sets none
SAMPLE_LEN = 16


class Block(NamedTuple):
    sizes: Tuple[str, ...]  # what it takes beyond ``SHARED``
    #: (its sizes by name, ``attn(precision=None) -> attn_fn``) -> the
    #: flax module, after checking what the sizes must satisfy
    make: Callable[[Dict[str, Any], Callable[..., Any]], Any]
    loss: str = HEAD_NLL
    #: HEAD_NLL: the step returns what the sparse layers ``sow`` beside
    #: the loss (``lm/model.py`` ``MOE_STATS``)
    sown_stats: bool = False
    #: positions of the initialisation's sample; 0: ``seq_len``.  With
    #: rotary positions no parameter depends on it, and a host role's
    #: forward pass with the materialised reference attention at a
    #: training sequence takes minutes and tens of GB (``lm_layout`` on
    #: a server rank): short but for the two oldest, which keep theirs.
    sample_len: int = SAMPLE_LEN
    #: the module has ``kept_residual_bytes(seq_len, flash)``: what its
    #: checkpoints keep by name for the backward pass, a sequence
    kept_residuals: bool = False


SHARED = ("d_model", "n_heads", "n_layers", "seq_len", "vocab")
_ROTARY = ("rope_theta", "norm_eps")
_GROUPED = ("kv_heads", "head_dim")
_SPARSE = ("n_experts", "experts_per_tok", "expert_width")
_SHARE = ("experts_first", "experts_held")


def _transformer() -> Any:
    from mpit_tpu.models import transformer  # when a maker runs, not before

    return transformer


def _module(decoder: str, sizes: Mapping[str, Any], attn_fn: Any,
            **derived: Any) -> Any:
    """The decoder of that name with every size that is a field of it
    under the size's name, ``derived`` laid over them."""
    cls = getattr(_transformer(), decoder)
    fields = {field.name for field in dataclasses.fields(cls)}
    return cls(**{**{name: value for name, value in sizes.items()
                     if name in fields}, **derived}, attn_fn=attn_fn)


def _heads(s: Mapping[str, Any]) -> Dict[str, int]:
    return {"kv_heads": s["kv_heads"] or s["n_heads"],
            "head_dim": s["head_dim"] or s["d_model"] // s["n_heads"]}


def _check_share(s: Mapping[str, Any]) -> None:
    first, held = s["experts_first"], s["experts_held"] or s["n_experts"]
    if first + held > s["n_experts"]:
        raise ValueError(f"experts {first}..{first + held - 1} held of "
                         f"{s['n_experts']}")


def _gpt2(s, attn):
    return _module("TinyDecoder", s, attn(), max_len=s["seq_len"])


def _olmoe(s, attn):
    # read at build time: the probe of the reference's tolerances tries
    # other precisions (chipbench/reference/probe_olmoe.py)
    return _module("OlmoeDecoder", s,
                   attn(_transformer().ATTN_KERNEL_PRECISION))


def _mellum(s, attn):
    _check_share(s)
    yarn = (s["yarn_factor"], s["yarn_orig"], s["yarn_beta_fast"],
            s["yarn_beta_slow"], s["yarn_attn_factor"]
            ) if s["yarn_factor"] else None
    return _module("MellumDecoder", s, attn(), yarn=yarn, **_heads(s))


def _layer_kinds(s: Mapping[str, Any]) -> Tuple[str, ...]:
    kinds = tuple(kind.strip() for kind in s["layer_types"].split(",")
                  if kind.strip())
    if len(kinds) != s["n_layers"]:
        raise ValueError(f"layer_types names {len(kinds)} layers "
                         f"({s['layer_types']!r}), n_layers is "
                         f"{s['n_layers']}")
    return kinds


def _lfm2(s, attn):
    _check_share(s)
    return _module("Lfm2Decoder", s, attn(), layer_types=_layer_kinds(s),
                   **_heads(s))


def _ouro(s, attn):
    if s["loop_steps"] < 1:
        raise ValueError(f"loop_steps {s['loop_steps']}: at least one pass")
    return _module("OuroDecoder", s, attn(), **_heads(s))


def _joyai(s, attn):
    _check_share(s)
    latent = tuple(s[name] for name in (
        "q_rank", "kv_rank", "qk_nope", "qk_rope", "v_head"))
    if min(latent) < 1 or s["qk_rope"] % 2:
        raise ValueError(f"joyai needs q_rank, kv_rank, qk_nope, qk_rope "
                         f"(even) and v_head: {latent}")
    return _module("JoyaiDecoder", s, attn())


def _kimi(s, attn):
    _check_share(s)
    kinds = _layer_kinds(s)
    latent = tuple(s[name] for name in (
        "kv_rank", "qk_nope", "qk_rope", "v_head", "kda_heads",
        "kda_head_dim"))
    if min(latent) < 1 or s["q_rank"] < 0 or s["qk_rope"] % 2:
        raise ValueError(f"kimi needs kv_rank, qk_nope, qk_rope (even), "
                         f"v_head, kda_heads and kda_head_dim: {latent}; "
                         f"q_rank {s['q_rank']} (0: no query latent)")
    return _module("KimiDecoder", s, attn(), layer_types=kinds)


def _keye(s, attn):
    _check_share(s)
    index = tuple(s[name] for name in (
        "index_heads", "index_head_dim", "index_topk"))
    if min(index) < 1 or s["index_head_dim"] % 2:
        raise ValueError(f"keye needs index_heads, index_head_dim (even) "
                         f"and index_topk: {index}")
    return _module("KeyeDecoder", s, attn(), **_heads(s))


def _sdar(s, attn):
    _check_share(s)
    block, seq = s["block_len"], s["seq_len"]
    # whole blocks in the training sequence and in the initialisation's
    # sample alike
    if (block < 1 or block & (block - 1) or SAMPLE_LEN % block
            or seq % block):
        raise ValueError(f"sdar needs block_len a power of two up to "
                         f"{SAMPLE_LEN} that divides seq_len {seq}: {block}")
    if not -1 <= s["mask_id"] < s["vocab"]:
        raise ValueError(f"mask_id {s['mask_id']} is no row of a table of "
                         f"{s['vocab']} (-1: the last)")
    return _module("SdarDecoder", s, attn(), **_heads(s))


def _trinity(s, attn):
    _check_share(s)
    if s["window"] < 1 or s["bias_rate"] < 0 or s["embed_scale"] <= 0:
        raise ValueError(f"trinity needs a window, a bias_rate of 0 or "
                         f"more and an embed_scale over 0: "
                         f"{s['window']}, {s['bias_rate']}, "
                         f"{s['embed_scale']}")
    return _module("TrinityDecoder", s, attn(), layer_types=_layer_kinds(s),
                   **_heads(s))


def _nemotron(s, attn):
    _check_share(s)
    kinds = _layer_kinds(s)
    state = tuple(s[name] for name in (
        "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk"))
    if min(state) < 1 or s["ssm_heads"] % s["ssm_groups"] \
            or s["init_depth"] < 0 or s["shared_width"] < 0:
        raise ValueError(f"nemotron needs ssm_heads, ssm_head_dim, "
                         f"ssm_groups (dividing the heads), ssm_state and "
                         f"ssm_chunk: {state}; init_depth "
                         f"{s['init_depth']} and shared_width "
                         f"{s['shared_width']} of 0 or more")
    return _module("NemotronDecoder", s, attn(), layer_types=kinds,
                   **_heads(s))


def _qwen3next(s, attn):
    _check_share(s)
    kinds = _layer_kinds(s)
    state = tuple(s[name] for name in (
        "gdn_key_heads", "gdn_value_heads", "gdn_key_dim", "gdn_value_dim"))
    rotary = (s["head_dim"] or s["d_model"] // s["n_heads"]
              ) * s["rotary_factor"]
    if min(state) < 1 or s["gdn_value_heads"] % s["gdn_key_heads"] \
            or s["shared_width"] < 0 or rotary < 2 or rotary % 2:
        raise ValueError(f"qwen3next needs gdn_key_heads, gdn_value_heads "
                         f"(a multiple of them), gdn_key_dim and "
                         f"gdn_value_dim: {state}; shared_width "
                         f"{s['shared_width']} of 0 or more; rotary_factor "
                         f"{s['rotary_factor']} of the head an even count "
                         f"of dimensions: {rotary}")
    return _module("Qwen3NextDecoder", s, attn(), layer_types=kinds,
                   **_heads(s))


def _granite(s, attn):
    kinds = _layer_kinds(s)
    state = tuple(s[name] for name in (
        "ssm_heads", "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk",
        "dense_width"))
    scales = tuple(s[name] for name in (
        "embed_scale", "residual_scale", "logits_scale"))
    if min(state) < 1 or s["ssm_heads"] % s["ssm_groups"] \
            or min(scales) <= 0 or s["attn_scale"] < 0:
        raise ValueError(f"granite needs ssm_heads, ssm_head_dim, "
                         f"ssm_groups (dividing the heads), ssm_state, "
                         f"ssm_chunk and dense_width: {state}; embed_scale, "
                         f"residual_scale and logits_scale over 0: "
                         f"{scales}; attn_scale {s['attn_scale']} of 0 "
                         f"or more")
    return _module("GraniteDecoder", s, attn(), layer_types=kinds,
                   **_heads(s))


# what each block is: its decoder's docstring (``models/transformer.py``)
BLOCKS: Dict[str, Block] = {
    "gpt2": Block((), _gpt2, sample_len=0),
    "olmoe": Block(_SPARSE + _ROTARY, _olmoe, sown_stats=True, sample_len=0),
    "mellum": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "window", "full_every", "yarn_factor", "yarn_orig",
            "yarn_beta_fast", "yarn_beta_slow", "yarn_attn_factor"),
        _mellum, sown_stats=True),
    "lfm2": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "layer_types", "dense_layers", "dense_width", "conv_kernel",
            "route_scale"),
        _lfm2, sown_stats=True),
    "ouro": Block(
        _GROUPED + _ROTARY + ("dense_width", "loop_steps", "exit_beta",
                              "exit_bias"),
        _ouro, loss=OWN_LOSS, kept_residuals=True),
    "joyai": Block(
        _SPARSE + _SHARE + _ROTARY + (
            "dense_layers", "dense_width", "route_scale", "q_rank",
            "kv_rank", "qk_nope", "qk_rope", "v_head", "shared_experts",
            "mtp_layers", "mtp_weight"),
        _joyai, loss=OWN_LOSS),
    "kimi": Block(
        _SPARSE + _SHARE + _ROTARY + (
            "layer_types", "conv_kernel", "kda_heads", "kda_head_dim",
            "dense_layers", "dense_width", "route_scale", "q_rank",
            "kv_rank", "qk_nope", "qk_rope", "v_head", "shared_experts"),
        _kimi, loss=OWN_LOSS),
    "keye": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "index_heads", "index_head_dim", "index_topk"),
        _keye, loss=OWN_LOSS),
    "sdar": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "block_len", "mask_id", "noise_seed"),
        _sdar, loss=OWN_LOSS),
    "trinity": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "layer_types", "window", "dense_layers", "dense_width",
            "route_scale", "shared_experts", "bias_rate", "embed_scale"),
        _trinity, loss=OWN_LOSS),
    "nemotron": Block(
        _GROUPED + _SPARSE + _SHARE + (
            "norm_eps", "layer_types", "conv_kernel", "ssm_heads",
            "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk",
            "route_scale", "shared_experts", "shared_width", "init_depth"),
        _nemotron, loss=OWN_LOSS),
    "qwen3next": Block(
        _GROUPED + _SPARSE + _SHARE + _ROTARY + (
            "layer_types", "conv_kernel", "gdn_key_heads",
            "gdn_value_heads", "gdn_key_dim", "gdn_value_dim",
            "rotary_factor", "shared_experts", "shared_width"),
        _qwen3next, loss=OWN_LOSS),
    "granite": Block(
        _GROUPED + (
            "norm_eps", "layer_types", "conv_kernel", "ssm_heads",
            "ssm_head_dim", "ssm_groups", "ssm_state", "ssm_chunk",
            "dense_width", "embed_scale", "residual_scale", "attn_scale",
            "logits_scale"),
        _granite, loss=OWN_LOSS),
}
ARCHS = tuple(BLOCKS)


def block(arch: str) -> Block:
    """The table's lookup."""
    try:
        return BLOCKS[arch]
    except KeyError:
        raise ValueError(f"unknown LM arch {arch!r}; have {ARCHS}") from None


def sizes_of(arch: str) -> Tuple[str, ...]:
    """The sizes ``arch`` takes by name: every block's, then its own."""
    return SHARED + block(arch).sizes


def resolve(arch: str, given: Mapping[str, Any]) -> Dict[str, Any]:
    """Every size ``arch`` takes, by name: ``given`` where it names one,
    else the size's default, each in the size's type.  A size the block
    does not take is refused by naming the ones it does."""
    names = sizes_of(arch)
    stray = sorted(set(given) - set(names))
    if stray:
        raise TypeError(f"{arch} takes no {', '.join(stray)}; its sizes are "
                        f"{', '.join(names)}")
    sizes = {name: type(DEFAULTS[name])(given.get(name, DEFAULTS[name]))
             for name in names}
    sizes["vocab"] = sizes["vocab"] or 256
    return sizes
