"""Representative op graphs for layout propagation, layout search, and
compiled execution.

``decoder_layer_graph`` builds the op graph of one decoder layer for a
model-zoo config; ``model_graph`` builds the whole-model graph — embed →
N decoder layers → lm_head — with family variants: dense / MoE
(dispatch + expert GEMMs + combine), SSM and hybrid mixers
(Mamba2/Jamba), and the encoder–decoder stack with cross-attention
(Whisper). Reshape boundaries are *in-graph* ``reshape`` nodes, so a
sharding a reshape cannot carry is paid for as an AllGather in the plan
rather than silently dropped.

Every graph is a :class:`GraphSpec`: the node list, per-input tensor
metadata (shape / dtype / role / the rule engine's seeded preference
list), and the physical space. ``seeded_env()`` resolves the preference
lists through ``rules.pick_spec`` — that is the baseline plan the layout
solver (``repro.axe.solve``) has to beat; the solver itself enumerates
placements from the spec algebra instead of the preference lists.

Since ``axe.compile`` these graphs are *executable*: every node carries
the execution attrs its backend needs (norm weights, rope/qk-norm/mask
parameters on the q/k/v boundary nodes, router + capacity metadata on
the MoE nodes, the SSD mixer's auxiliary tensors) referencing small
replicated auxiliary parameters by name, and records where each param,
cache and auxiliary tensor is stored (:class:`Stored`), which is all the
binding in ``axe.compile`` reads. Projections are split exactly
as the reference models keep them (``wq``/``wk``/``wv``, the SwiGLU
``wg``/``wu`` pair, per-expert ``moe_wg``/``moe_wu``) so a solved
placement of a graph weight is directly a placement of the model leaf
and the local shards line up with head/feature boundaries. The
propagation rules ignore attrs they do not read, so the layout
semantics stay those of the plain op kinds.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.axe import rules
from repro.axe.propagate import OpNode
from repro.axe.spec import AxeSpec, PhysicalSpace


@dataclasses.dataclass(frozen=True)
class Stored:
    """Where a graph tensor lives in the model's stored pytrees
    (``models.transformer``): the ``tree`` ("params" or "cache"), the key
    ``path`` into it, the super-block ``block`` whose entry of the stacked
    leaf it is (None: the leaf is not stacked), and the ``view`` that
    gives the graph's shape:

    * ``"slice"`` — the entry as it is;
    * ``"cols"`` — heads merged into columns (``wq [d, H, hd] → [d, H·hd]``);
    * ``"rows"`` — heads merged into rows (``wo [H, hd, d] → [H·hd, d]``);
    * ``"T"`` — transposed (the tied head: ``embed [V, d]`` as ``[d, V]``);
    * ``"experts"`` — a layer's expert stack ``[E, d, f]``.

    ``axe.compile``'s binder reads these records and nothing else."""

    tree: str
    path: Tuple[str, ...]
    block: Optional[int] = None
    view: str = "slice"


@dataclasses.dataclass(frozen=True)
class TensorMeta:
    """One graph input: logical shape + dtype + the seeded preference
    list (``rules`` syntax) the baseline plan resolves it with, and where
    a ``param``/``cache`` input is stored (None where no binder reads it:
    the encoder–decoder family's weights)."""

    name: str
    shape: Tuple[int, ...]
    dtype: str
    role: str                      # "activation" | "param" | "cache"
    prefs: Tuple[Tuple, ...] = ()
    stored: Optional[Stored] = None


@dataclasses.dataclass
class GraphSpec:
    """An op graph plus everything needed to seed or solve its layout.

    ``extra_outputs`` names tensors that are graph results *in addition
    to* being consumed by later nodes — the cache-out boundary of the
    decode-step graphs, where the updated KV cache both feeds the
    attention node and must leave the executable for the next step.

    ``aux`` records where each auxiliary tensor an execution attr names
    (norm weights, the router, the SSD mixer's constants) is stored;
    ``cache_out`` maps each cache-out tensor to the cache input it
    replaces."""

    nodes: List[OpNode]
    inputs: Dict[str, TensorMeta]
    space: PhysicalSpace
    extra_outputs: Tuple[str, ...] = ()
    aux: Dict[str, Stored] = dataclasses.field(default_factory=dict)
    cache_out: Dict[str, str] = dataclasses.field(default_factory=dict)

    def seeded_env(self) -> Dict[str, AxeSpec]:
        """The rule-engine baseline: first admissible preference per
        input (replication when nothing in the list is admissible)."""
        env: Dict[str, AxeSpec] = {}
        for m in self.inputs.values():
            if m.prefs:
                env[m.name] = rules.pick_spec(m.shape, m.prefs, self.space, m.dtype)
            else:
                env[m.name] = AxeSpec.replicated(m.shape, self.space, m.dtype)
        return env

    def outputs(self) -> Tuple[str, ...]:
        """Tensors produced but never consumed (the graph results),
        plus any declared ``extra_outputs`` — in node order."""
        consumed = {i for n in self.nodes for i in n.inputs}
        extra = set(self.extra_outputs)
        return tuple(
            n.out for n in self.nodes
            if n.out not in consumed or n.out in extra
        )


class _Builder:
    """Accumulates nodes + input metadata while building one graph."""

    def __init__(self, space: PhysicalSpace, dtype: str):
        self.space = space
        self.dtype = dtype
        self.nodes: List[OpNode] = []
        self.inputs: Dict[str, TensorMeta] = {}
        self.extra_outputs: List[str] = []
        self.aux: Dict[str, Stored] = {}
        self.cache_out: Dict[str, str] = {}

    def inp(self, name: str, shape, role: str, prefs=(), dtype=None,
            stored: Optional[Stored] = None) -> str:
        self.inputs[name] = TensorMeta(
            name, tuple(int(s) for s in shape), dtype or self.dtype, role,
            tuple(tuple(p) for p in prefs), stored,
        )
        return name

    def stored_aux(self, name: str, stored: Optional[Stored]) -> str:
        """Name an auxiliary tensor (for an execution attr) and record
        where it is stored."""
        if stored is not None:
            self.aux[name] = stored
        return name

    def op(self, name: str, kind: str, ins, out: str, attrs=()) -> str:
        self.nodes.append(OpNode(name, kind, tuple(ins), out, tuple(attrs)))
        return out

    def reshape(self, name: str, src: str, shape, carry, extra=()) -> str:
        return self.op(
            name, "reshape", (src,), name,
            attrs=(("shape", tuple(int(s) for s in shape)),
                   ("carry", tuple(tuple(c) for c in carry)))
            + tuple(extra),
        )

    def mark_output(self, name: str) -> str:
        self.extra_outputs.append(name)
        return name

    def spec(self) -> GraphSpec:
        return GraphSpec(self.nodes, self.inputs, self.space,
                         tuple(self.extra_outputs), self.aux, self.cache_out)


def _param(layer, *keys: str, view: str = "slice") -> Optional[Stored]:
    """The stored param ``blocks/l{slot}/<keys>`` of decoder layer
    ``layer`` (a ``cfg.layer(i)``; None: a block no binder reads)."""
    if layer is None:
        return None
    return Stored("params", ("blocks", f"l{layer.slot}", *keys), layer.block, view)


def _cache(b: _Builder, layer, p: str, key: str, shape, prefs, dtype=None) -> str:
    """Layer ``layer``'s cache input for the stored cache leaf
    ``l{slot}/<key>``, named by ``rules.CACHE_GRAPH_NAMES``."""
    return b.inp(f"{p}{rules.CACHE_GRAPH_NAMES[key]}", shape, "cache", prefs,
                 dtype=dtype,
                 stored=Stored("cache", (f"l{layer.slot}", key), layer.block))


# ---------------------------------------------------------------------------
# per-layer builders
# ---------------------------------------------------------------------------


def _attention_block(
    b: _Builder, cfg, batch: int, seq: int, p: str, x_in: str,
    *, layer=None, causal: bool = True,
    kv_from: str = None, kv_tokens: int = None, kv_seq: int = None,
) -> str:
    """norm → q/k/v projections → attention → output projection →
    residual. ``kv_from`` switches to cross-attention: K/V project from
    that tensor (the encoder output) instead of the normed input.
    ``layer`` (``cfg.layer(i)``) windows the attention and locates the
    stored weights; None for encoder layers and cross-attention."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = batch * seq
    cross = kv_from is not None
    x_n = _norm_in(b, layer, p, x_in)
    kv_s = seq if not cross else (
        kv_seq if kv_seq is not None else (kv_tokens // batch)
    )
    qf, kf, vf = _qkv_proj(b, cfg, layer, p, x_n, kv_from if cross else x_n,
                           "c" if cross else "")
    q = b.reshape(f"{p}q", qf, (batch, h, seq, hd), ((0, 0), (1, 1)),
                  extra=_select(b, cfg, layer, p, "q", batch, cross))
    k = b.reshape(f"{p}k", kf, (batch, kv, kv_s, hd), ((0, 0), (1, 1)),
                  extra=_select(b, cfg, layer, p, "k", batch, cross))
    v = b.reshape(f"{p}v", vf, (batch, kv, kv_s, hd), ((0, 0), (1, 1)),
                  extra=_select(b, cfg, layer, p, "v", batch, cross))
    attn = b.op(f"{p}attention", "attention", (q, k, v), f"{p}attn_out",
                attrs=(("causal", causal and not cross),
                       ("window", None if cross or layer is None else layer.window)))
    flat = b.reshape(f"{p}attn_flat", attn, (t, h * hd), ((0, 0), (1, 1)),
                     extra=(("select", "merge_heads"), ("batch", batch)))
    return _attn_out(b, cfg, layer, p, flat, x_in, "c" if cross else "")


def _norm_in(b: _Builder, layer, p: str, x_in: str) -> str:
    return b.op(f"{p}norm_in", "norm", (x_in,), f"{p}x_n",
                attrs=(("weight", b.stored_aux(f"{p}norm1", _param(layer, "norm1"))),))


def _qkv_proj(b: _Builder, cfg, layer, p: str, x_n: str, kv_src: str, c: str = ""):
    """The q/k/v weights and projections. Cross-attention weights
    (``c="c"``) get non-colliding base names (cwq/cwk/...) so PlanRules
    never mistakes them for the self-attention projections."""
    d, hd = cfg.d_model, cfg.head_dim
    out = []
    for w, heads, src in (("q", cfg.num_heads, x_n), ("k", cfg.num_kv_heads, kv_src),
                          ("v", cfg.num_kv_heads, kv_src)):
        wt = b.inp(f"{p}{c}w{w}", (d, heads * hd), "param",
                   [(None, "model"), (None, None)],
                   stored=_param(layer, "attn", f"w{w}", view="cols"))
        out.append((src, wt, w))
    return tuple(b.op(f"{p}{w}_proj", "matmul", (src, wt), f"{p}{w}f")
                 for src, wt, w in out)


def _attn_out(b: _Builder, cfg, layer, p: str, flat: str, x_in: str, c: str = "") -> str:
    """Output projection → residual."""
    hd, h = cfg.head_dim, cfg.num_heads
    wo = b.inp(f"{p}{c}wo", (h * hd, cfg.d_model), "param",
               [("model", None), (None, None)],
               stored=_param(layer, "attn", "wo", view="rows"))
    o = b.op(f"{p}wo_proj", "matmul", (flat, wo), f"{p}attn_o")
    return b.op(f"{p}attn_residual", "elementwise", (o, x_in), f"{p}x1",
                attrs=(("fn", "add"),))


def _select(b: _Builder, cfg, layer, p: str, role: str, batch: int,
            cross: bool = False):
    """The execution attrs of the q/k/v boundary: the reference models
    split heads there, rotary-embed q and k, and qk-norm them (never for
    cross-attention)."""
    heads = cfg.num_heads if role == "q" else cfg.num_kv_heads
    rope = role != "v" and not cross
    attrs = (("select", role), ("heads", heads), ("head_dim", cfg.head_dim),
             ("batch", batch), ("rope_theta", cfg.rope_theta if rope else None))
    if role == "v":
        return attrs
    norm = (b.stored_aux(f"{p}{role}_norm", _param(layer, "attn", f"{role}_norm"))
            if cfg.qk_norm and not cross else None)
    return attrs + (("norm_weight", norm),)


def _ssm_in(b: _Builder, cfg, layer, p: str, x_in: str):
    """The SSD mixer's input half: norm → x/z/B/C/dt projections. Returns
    the projections and the mixer's execution attrs (its constants)."""
    d = cfg.d_model
    di, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    x_n = _norm_in(b, layer, p, x_in)
    outs = []
    for w, shape, prefs, proj, out in (
            ("wx", (d, di), [(None, "model"), (None, None)], "x_proj", "xz"),
            ("wz", (d, di), [(None, "model"), (None, None)], "z_proj", "zz"),
            ("wB", (d, n), [(None, None)], "b_proj", "bb"),
            ("wC", (d, n), [(None, None)], "c_proj", "cc"),
            ("wdt", (d, h), [(None, "model"), (None, None)], "dt_proj", "dt")):
        outs.append((b.inp(f"{p}{w}", shape, "param", prefs,
                           stored=_param(layer, "ssm", w)), proj, out))
    projs = tuple(b.op(f"{p}{proj}", "matmul", (x_n, w), f"{p}{out}")
                  for w, proj, out in outs)
    consts = tuple((c, b.stored_aux(f"{p}{c}", _param(layer, "ssm", c)))
                   for c in ("dt_bias", "A_log", "D", "conv_w"))
    return projs, (("heads", h), ("head_dim", cfg.ssm_headdim),
                   ("state", n), ("d_inner", di)) + consts


def _ssm_out(b: _Builder, cfg, layer, p: str, y: str, zz: str, x_in: str) -> str:
    """The SSD mixer's output half: gate → gated norm → out proj →
    residual."""
    g = b.op(f"{p}gate", "elementwise", (y, zz), f"{p}g",
             attrs=(("fn", "mul_silu"),))
    gn = b.op(f"{p}gate_norm", "norm", (g,), f"{p}gn",
              attrs=(("weight", b.stored_aux(f"{p}gate_norm",
                                             _param(layer, "ssm", "gate_norm"))),))
    wo = b.inp(f"{p}ssm_wo", (cfg.ssm_d_inner, cfg.d_model), "param",
               [("model", None), (None, None)], stored=_param(layer, "ssm", "wo"))
    o = b.op(f"{p}out_proj", "matmul", (gn, wo), f"{p}ssm_o")
    return b.op(f"{p}ssm_residual", "elementwise", (o, x_in), f"{p}x1",
                attrs=(("fn", "add"),))


def _ssm_block(b: _Builder, cfg, batch: int, seq: int, p: str, x_in: str,
               layer=None) -> str:
    """norm → (x/z/B/C/dt projections) → SSD mix → gate → gated norm →
    out proj → residual; the Mamba2 mixer as layout ops."""
    (xz, zz, bb, cc, dt), attrs = _ssm_in(b, cfg, layer, p, x_in)
    y = b.op(f"{p}ssm_mix", "ssm_mix", (xz, bb, cc, dt), f"{p}y",
             attrs=(("batch", batch), ("seq", seq)) + attrs)
    return _ssm_out(b, cfg, layer, p, y, zz, x_in)


def _ffn_block(b: _Builder, cfg, t: int, p: str, x_in: str, res: str,
               layer=None) -> str:
    """norm → dense FFN or MoE dispatch/expert-GEMMs/combine → residual.

    The FFN keeps the reference models' structure — a SwiGLU gate pair
    (``wg``/``wu``) or a single GELU projection, per ``cfg.mlp_type`` —
    so the plan accounts for both GEMMs and the compiled executor
    reproduces the exact activation math."""
    d = cfg.d_model
    x2 = b.op(f"{p}norm_ffn", "norm", (x_in,), f"{p}x2",
              attrs=(("weight", b.stored_aux(f"{p}norm2", _param(layer, "norm2"))),))
    if cfg.is_moe:
        # the experts held here (all, or this device's share); the router
        # keeps every expert. One buffer row per token: no drops
        e, f_e = cfg.moe_experts_held, cfg.moe_d_ff
        moe_wg = b.inp(f"{p}moe_wg", (e, d, f_e), "param",
                       [("model", None, None), (None, None, "model"),
                        (None, None, None)],
                       stored=_param(layer, "moe", "wg", view="experts"))
        moe_wu = b.inp(f"{p}moe_wu", (e, d, f_e), "param",
                       [("model", None, None), (None, None, "model"),
                        (None, None, None)],
                       stored=_param(layer, "moe", "wu", view="experts"))
        moe_wo = b.inp(f"{p}moe_wo", (e, f_e, d), "param",
                       [("model", None, None), (None, "model", None),
                        (None, None, None)],
                       stored=_param(layer, "moe", "wo", view="experts"))
        router = b.stored_aux(f"{p}router", _param(layer, "moe", "router"))
        xe = b.op(f"{p}moe_dispatch", "moe_dispatch", (x2,), f"{p}xe",
                  attrs=(("experts", e), ("capacity", t),
                         ("experts_per_tok", cfg.experts_per_tok),
                         ("expert_offset", cfg.expert_offset),
                         ("router", router)))
        hg = b.op(f"{p}moe_ffn_g", "matmul", (xe, moe_wg), f"{p}hg")
        hu = b.op(f"{p}moe_ffn_u", "matmul", (xe, moe_wu), f"{p}hu")
        ha = b.op(f"{p}moe_act", "elementwise", (hg, hu), f"{p}ha",
                  attrs=(("fn", "swiglu"),))
        oe = b.op(f"{p}moe_ffn_out", "matmul", (ha, moe_wo), f"{p}oe")
        out = b.op(f"{p}moe_combine", "moe_combine", (oe,), f"{p}moe_out",
                   attrs=(("tokens", t), ("dispatch", f"{p}xe"),
                          ("dispatch_input", f"{p}x2"),
                          ("experts", e), ("capacity", t)))
        return b.op(f"{p}ffn_residual", "elementwise", (out, res), f"{p}x_out",
                    attrs=(("fn", "add"),))
    if cfg.mlp_type == "swiglu":
        wg = b.inp(f"{p}wg", (d, cfg.d_ff), "param", [(None, "model"), (None, None)],
                   stored=_param(layer, "mlp", "wg"))
        wu = b.inp(f"{p}wu", (d, cfg.d_ff), "param", [(None, "model"), (None, None)],
                   stored=_param(layer, "mlp", "wu"))
        hg = b.op(f"{p}ffn_g", "matmul", (x2, wg), f"{p}hgd")
        hu = b.op(f"{p}ffn_u", "matmul", (x2, wu), f"{p}hud")
        hh = b.op(f"{p}ffn_act", "elementwise", (hg, hu), f"{p}ffn_h",
                  attrs=(("fn", "swiglu"),))
    else:
        wi = b.inp(f"{p}wi", (d, cfg.d_ff), "param", [(None, "model"), (None, None)],
                   stored=_param(layer, "mlp", "wi"))
        h0 = b.op(f"{p}ffn_in", "matmul", (x2, wi), f"{p}ffn_h0")
        hh = b.op(f"{p}ffn_act", "elementwise", (h0,), f"{p}ffn_h",
                  attrs=(("fn", "gelu"),))
    wo2 = b.inp(f"{p}wo2", (cfg.d_ff, d), "param", [("model", None), (None, None)],
                stored=_param(layer, "mlp", "wo"))
    oo = b.op(f"{p}ffn_out", "matmul", (hh, wo2), f"{p}ffn_o")
    return b.op(f"{p}ffn_residual", "elementwise", (oo, res), f"{p}x_out",
                attrs=(("fn", "add"),))


def _decoder_layer(
    b: _Builder, cfg, batch: int, seq: int, p: str, x_in: str,
    *, layer_index: int = 0, enc_out: str = None, enc_tokens: int = None,
    enc_seq: int = None,
) -> str:
    """One decoder layer; returns the layer output tensor name."""
    t = batch * seq
    layer = cfg.layer(layer_index)
    if enc_out is not None:
        # encoder-decoder: cross-attention sub-block after self-attn.
        # models.encdec stores its layers another way: no records
        layer = None
        x1 = _attention_block(b, cfg, batch, seq, p, x_in)
        x1 = _attention_block(
            b, cfg, batch, seq, f"{p}cross.", x1,
            kv_from=enc_out, kv_tokens=enc_tokens, kv_seq=enc_seq,
        )
    elif layer.mixer == "ssm":
        x1 = _ssm_block(b, cfg, batch, seq, p, x_in, layer)
    else:
        x1 = _attention_block(b, cfg, batch, seq, p, x_in, layer=layer)
    if not (cfg.is_moe or cfg.d_ff):
        return x1  # pure SSM block (mamba2): mixer only
    return _ffn_block(b, cfg, t, p, x1, x1, layer)


def _embed(b: _Builder, cfg, tokens: str) -> str:
    embed = b.inp("embed", (cfg.vocab_size, cfg.d_model), "param",
                  list(rules.PARAM_RULES["embed"]),
                  stored=Stored("params", ("embed",)))
    return b.op("embed_lookup", "embed", (tokens, embed), "x0")


def _head(b: _Builder, cfg, x: str) -> str:
    """final norm → lm_head; a tied head is ``embed`` transposed."""
    x_f = b.op("final_norm", "norm", (x,), "x_f",
               attrs=(("weight", b.stored_aux("final_norm",
                                              Stored("params", ("final_norm",)))),))
    head = (Stored("params", ("embed",), view="T") if cfg.tie_embeddings
            else Stored("params", ("lm_head",)))
    lm_head = b.inp("lm_head", (cfg.d_model, cfg.vocab_size), "param",
                    list(rules.PARAM_RULES["lm_head"]), stored=head)
    return b.op("lm_head_proj", "matmul", (x_f, lm_head), "logits")


# ---------------------------------------------------------------------------
# public graph builders
# ---------------------------------------------------------------------------


def layer_graph_spec(
    cfg, batch: int, seq: int, space: PhysicalSpace, dtype: str = "bfloat16",
) -> GraphSpec:
    """One decoder layer as a :class:`GraphSpec` with a free activation
    input ``x`` — the single-layer graph ``dryrun --layout-plan`` and
    the propagation tests use."""
    b = _Builder(space, dtype)
    dp = rules.dp_entry(space)
    b.inp("x", (batch * seq, cfg.d_model), "activation",
          [(dp, None), (None, None)])
    _decoder_layer(b, cfg, batch, seq, "", "x")
    return b.spec()


def decoder_layer_graph(
    cfg,
    batch: int,
    seq: int,
    space: PhysicalSpace,
    dtype: str = "bfloat16",
) -> Tuple[List[OpNode], Dict[str, AxeSpec]]:
    """One decoder layer as (nodes, seeded input specs) for
    ``propagate`` — the historical entry point, now a view over
    :func:`layer_graph_spec`. Reshape boundaries are in-graph nodes, so
    placements the new extents do not admit (GQA kv heads, non-dividing
    head counts) cost an AllGather in the plan instead of being dropped
    silently."""
    gs = layer_graph_spec(cfg, batch, seq, space, dtype)
    return gs.nodes, gs.seeded_env()


def model_graph(
    cfg,
    batch: int,
    seq: int,
    space: PhysicalSpace,
    dtype: str = "bfloat16",
    *,
    layers: int = 2,
) -> GraphSpec:
    """The whole-model op graph: embed → ``layers`` decoder layers →
    final norm → lm_head, with the family variants (MoE, SSM/hybrid
    mixers, encoder–decoder cross-attention). ``layers`` caps the
    decoder depth (layout plans repeat per layer; two layers exercise
    every cross-layer boundary)."""
    b = _Builder(space, dtype)
    dp = rules.dp_entry(space)
    t = batch * seq

    tokens = b.inp("tokens", (t,), "activation", [(dp,), (None,)], dtype="int32")
    x = _embed(b, cfg, tokens)

    enc_out = None
    enc_t = enc_s = None
    if cfg.family == "encdec":
        enc_s = cfg.encoder_seq
        enc_t = batch * enc_s
        frames = b.inp("frames", (enc_t, cfg.d_model), "activation",
                       [(dp, None), (None, None)])
        e_x = frames
        for i in range(min(cfg.encoder_layers, layers)):
            p = f"E{i}."
            e_x1 = _attention_block(b, cfg, batch, enc_s, p, e_x, causal=False)
            e_x = _ffn_block(b, cfg, enc_t, p, e_x1, e_x1)
        enc_out = b.op("enc_norm", "norm", (e_x,), "enc_out",
                       attrs=(("weight", "enc_norm"),))

    n_layers = min(cfg.num_layers, layers)
    for i in range(n_layers):
        x = _decoder_layer(
            b, cfg, batch, seq, f"L{i}.", x,
            layer_index=i, enc_out=enc_out, enc_tokens=enc_t, enc_seq=enc_s,
        )

    _head(b, cfg, x)
    return b.spec()


# ---------------------------------------------------------------------------
# decode-step graphs: the KV cache as a first-class graph tensor
# ---------------------------------------------------------------------------

#: causal-conv filter taps — the jax-free twin of ``models.ssm.CONV_K``
#: (parity asserted in tests) so the conv-state cache input matches the
#: reference ``ssd_state_init`` leaf exactly
CONV_K = 4


def cache_window(cfg, layer_index: int, max_seq: int) -> int:
    """The cache length of one layer: its sliding window (ring buffer)
    capped at ``max_seq``, or the full ``max_seq`` — exactly
    ``models.transformer.cache_init``'s per-layer allocation."""
    w = cfg.layer(layer_index).window
    return min(w, max_seq) if w else max_seq


def _attention_decode_block(
    b: _Builder, cfg, batch: int, max_seq: int, p: str, x_in: str, layer,
) -> str:
    """One decode step of the attention mixer: norm → q/k/v projections
    → rope/qk-norm at the *runtime* position (``decode_select``) → cache
    write at that position (``cache_update`` — the cache-in/cache-out
    boundary) → single-token attention over the laid-out cache
    (``decode_attention``) → output projection → residual."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    w_len = min(layer.window, max_seq) if layer.window else max_seq
    ring = layer.window is not None
    x_n = _norm_in(b, layer, p, x_in)
    qf, kf, vf = _qkv_proj(b, cfg, layer, p, x_n, x_n)
    q, k, v = (b.op(f"{p}{r}", "decode_select", (src, "pos"), f"{p}{r}",
                    attrs=_select(b, cfg, layer, p, r, batch))
               for r, src in (("q", qf), ("k", kf), ("v", vf)))
    # cache-in: a first-class graph tensor the solver places like any
    # other (batch-sharded and/or kv-head-sharded; the ring/linear write
    # keeps the position dim locally complete)
    cache_prefs = [(rules.dp_entry(b.space), None, "model", None),
                   (None, None, "model", None),
                   (rules.dp_entry(b.space), None, None, None),
                   (None, None, None, None)]
    k_cache = _cache(b, layer, p, "k", (batch, w_len, kv, hd), cache_prefs)
    v_cache = _cache(b, layer, p, "v", (batch, w_len, kv, hd), cache_prefs)
    kco = b.op(f"{p}k_cache_write", "cache_update", (k_cache, k, "pos"),
               f"{p}k_cache_out", attrs=(("ring", ring),))
    vco = b.op(f"{p}v_cache_write", "cache_update", (v_cache, v, "pos"),
               f"{p}v_cache_out", attrs=(("ring", ring),))
    b.cache_out.update({kco: k_cache, vco: v_cache})
    b.mark_output(kco)
    b.mark_output(vco)
    attn = b.op(f"{p}decode_attention", "decode_attention",
                (q, kco, vco, "pos"), f"{p}attn_out",
                attrs=(("ring", ring),))
    flat = b.reshape(f"{p}attn_flat", attn, (batch, h * hd), ((0, 0), (1, 1)),
                     extra=(("select", "merge_heads"), ("batch", batch)))
    return _attn_out(b, cfg, layer, p, flat, x_in)


def _ssm_decode_block(b: _Builder, cfg, batch: int, p: str, x_in: str, layer) -> str:
    """One decode step of the SSD mixer: the recurrent state and the
    causal-conv history are cache-in tensors; ``ssm_decode`` advances
    them one token and the ``side_output`` boundary nodes surface the
    new states as graph outputs."""
    di, n, h = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads
    dp = rules.dp_entry(b.space)
    (xz, zz, bb, cc, dt), attrs = _ssm_in(b, cfg, layer, p, x_in)
    ssm_state = _cache(b, layer, p, "ssm", (batch, h, n, cfg.ssm_headdim),
                       [(dp, None, None, None), (None, None, None, None)],
                       dtype="float32")
    conv_state = _cache(b, layer, p, "conv", (batch, CONV_K - 1, di + 2 * n),
                        [(dp, None, None), (None, None, None)])
    y = b.op(f"{p}ssm_decode", "ssm_decode",
             (xz, bb, cc, dt, ssm_state, conv_state), f"{p}y",
             attrs=(("batch", batch),) + attrs)
    # cache-out boundary: the advanced states the mixer computed, typed
    # like their cache-in tensors
    for cin, channel in ((ssm_state, "ssm"), (conv_state, "conv")):
        out = b.op(f"{cin}_write", "side_output", (y,), f"{cin}_out",
                   attrs=(("side", y), ("channel", channel), ("like", cin)))
        b.cache_out[out] = cin
    return _ssm_out(b, cfg, layer, p, y, zz, x_in)


def decode_graph(
    cfg,
    batch: int,
    max_seq: int,
    space: PhysicalSpace,
    dtype: str = "bfloat16",
    *,
    layers: int = None,
) -> GraphSpec:
    """The single-token decode step as an op graph: embed the current
    token → per-layer mixers reading and writing their cache tensors at
    the runtime position ``pos`` → next-token logits.

    Activations are ``tokens [batch]`` and ``pos [batch]`` (per-slot
    positions, so a continuous batcher can decode requests at different
    depths in one step); cache tensors are named inputs
    (``L{i}.k_cache`` / ``L{i}.v_cache`` / ``L{i}.ssm_state`` /
    ``L{i}.conv_state``) shaped exactly like the reference
    ``cache_init`` leaves for one super-block slot, and the updated
    caches come back as graph outputs alongside ``logits``."""
    b = _Builder(space, dtype)
    dp = rules.dp_entry(space)

    tokens = b.inp("tokens", (batch,), "activation", [(dp,), (None,)], dtype="int32")
    b.inp("pos", (batch,), "activation", [(dp,), (None,)], dtype="int32")
    x = _embed(b, cfg, tokens)

    n_layers = cfg.num_layers if layers is None else min(cfg.num_layers, layers)
    for i in range(n_layers):
        p, layer = f"L{i}.", cfg.layer(i)
        if layer.mixer == "ssm":
            x = _ssm_decode_block(b, cfg, batch, p, x, layer)
        else:
            x = _attention_decode_block(b, cfg, batch, max_seq, p, x, layer)
        if cfg.is_moe or cfg.d_ff:
            x = _ffn_block(b, cfg, batch, p, x, x, layer)
        if cfg.is_moe:
            # the expert layer's load (``moe.load``): a graph output read
            # after serving, never inside a step
            b.op(f"{p}moe_load_out", "side_output", (f"{p}xe",), f"{p}moe_load",
                 attrs=(("side", f"{p}xe"), ("channel", "load"),
                        ("shape", (2,)), ("dtype", "int32")))

    _head(b, cfg, x)
    return b.spec()
