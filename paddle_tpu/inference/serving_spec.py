"""What a model hands the generation engine.

The engine schedules, allocates and dispatches; it knows no
architecture. A servable model has a `serving_spec()` that returns a
`ServingSpec`: the sizes the engine needs (vocabulary, longest context,
run dtype), WHAT STATE A SLOT HOLDS per kind of layer, the step
functions the compiled programs call, and the plans for the options a
model may or may not support (tensor parallel, int8 weights, adapters).

Three kinds of per-slot state live side by side in one manager
(`engine.PagedKVCache`):

* `paged_kv` — what the attention layers cache, paged in blocks that
  grow with the context. Either keys and values (`PagedKV`: how many
  layers, KV heads and the head size; the query heads only to choose the
  kernel): a K pool and a V pool `[layers, blocks, block, kv_heads,
  head_dim]`. Or ONE latent row a token a layer that every query head
  reads (`PagedLatent`: the row's width and how many of its leading
  values are the "value" part): one pool `[layers, blocks, block,
  row_width]`, no head axis and no second pool (`StepOut.vpool` is
  None). Blocks, refcounts, the prefix cache and copy-on-write are the
  same host code for both;
* `slot_state` — state of fixed size a slot (`SlotState`: recurrent
  layers' convolution window and state matrices), one row a slot in
  arrays `[layers, 1 + slots, ...]`, row 0 the null row that idle lanes
  write to, allocated, zeroed and freed with the slot.

A model none of whose layers caches by position sets `paged_kv=None`:
the engine then builds NO pool and no block table, allocates nothing a
step, and hands the step functions None for `kpool`, `vpool` and the
block tables; a slot is admitted when a state row is free, and a context
is limited by `max_seq_len` alone.

State of the second kind is not bounded by a position: a prefix hit, a
copy-on-write fork or a speculative window would each need a snapshot of
it. A spec lists under `refuses` what the engine must not serve for the
model, each with its reason, and the engine raises with that reason.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class PagedKV:
    layers: int            # attention layers that keep K and V
    kv_heads: int
    head_dim: int
    query_heads: int       # of one layer (kv_heads * group size)


@dataclass(frozen=True)
class PagedLatent:
    """One compressed row a cached token a layer, shared by all query
    heads (latent attention): `row_width` values of which the leading
    `value_width` are also what the probabilities sum (the rest only
    enter the scores: the rotated key)."""
    layers: int
    row_width: int
    value_width: int
    query_heads: int

    kv_heads = None        # a row has no head axis: one pool, no V

    @property
    def head_dim(self):
        return self.row_width


@dataclass(frozen=True)
class SlotState:
    name: str
    layers: int
    shape: tuple           # of one slot in one layer
    dtype: object


@dataclass
class StepOut:
    """What a step function returns: the hidden rows the head reads, the
    pools (and what rides beside them) as updated."""
    hidden: object
    kpool: object
    vpool: object                    # None where the spec keeps one pool
    kv_scales: object = None
    slot_state: tuple = ()
    counters: object = None          # int32 `[len(step_counters)]`


class ServingSpec:
    """Base: the sizes, and the refusals of a model that supports none of
    the options. A model's spec overrides what it serves."""

    #: feature -> reason; the engine raises a ValueError that gives it
    refuses: dict = {}
    #: `(name, "sum" | "max")` of the int32 counters a decode step
    #: returns (`StepOut.counters`), published as engine metrics. A spec
    #: that offers `decode_with_chunk` can tell how often it engaged: a
    #: counter that reads 1 from the fused step and 0 from the plain one
    step_counters: tuple = ()
    #: the same for what `prefill_chunk` returns in `StepOut.counters`,
    #: summed (or the largest) over every chunk launched
    #: (`engine.chunk_counter_totals`)
    chunk_counters: tuple = ()
    slot_state: tuple = ()

    def __init__(self, model, vocab_size, max_seq_len, dtype, paged_kv,
                 dropout=0.0):
        self.model = model
        self.vocab_size = int(vocab_size)
        self.max_seq_len = int(max_seq_len)
        self.dtype = dtype
        self.paged_kv = paged_kv
        self.dropout = float(dropout)

    # -- options a model may not have ------------------------------------
    def check_mesh(self, mp_degree, devices):
        """Raise unless the model's steps run over `mp_degree` shards."""
        raise ValueError(
            "this model's serving steps are not sharded: mp_degree "
            f"must be 1, got {mp_degree}")

    def tp_plan(self):
        raise NotImplementedError

    def weight_quant_plan(self):
        raise ValueError("this model has no int8 weight plan")

    def adapter_geometry(self):
        """`{name: size}` an adapter registry must match, or None where
        the model's steps take no adapters."""
        return None

    def attention_backend(self, requested, block_size, mp_degree):
        from paddle_tpu.ops.paged_attention import resolve_backend

        kv = self.paged_kv
        if kv.query_heads != kv.kv_heads:
            # the fused walk takes one KV head a query head
            if requested == "pallas":
                raise ValueError(
                    "the fused paged kernel does not serve grouped KV "
                    f"heads ({kv.query_heads} query heads on "
                    f"{kv.kv_heads}); use auto or dense")
            return resolve_backend("dense", kv.head_dim, block_size,
                                   kv.query_heads)
        return resolve_backend(requested, head_dim=kv.head_dim,
                               block_size=block_size,
                               num_heads=kv.query_heads // mp_degree)

    def decode_pages_per_step(self, block_size, mp_degree, pool_dtype):
        """Pool pages the fused decode walk fetches and scores a compute
        step (the engine's gauge of that name)."""
        from paddle_tpu.ops.pallas.paged_attention import pages_per_step

        kv = self.paged_kv
        if kv is None:
            return 0
        return pages_per_step(block_size, kv.kv_heads // mp_degree,
                              kv.head_dim, pool_dtype)

    # -- the step functions ------------------------------------------------
    def logits(self, hidden, mp_axis=None):
        raise NotImplementedError

    def prefill_chunk(self, tokens, start, kpool, vpool, block_row, plen,
                      **kw) -> StepOut:
        """`backend` among `kw` is the engine's one resolved attention
        backend, as `decode` takes it: a spec whose chunk has one form
        (a K/V pool's XLA loop) takes no notice of it."""
        raise NotImplementedError

    def decode(self, tokens, positions, kpool, vpool, block_tables,
               **kw) -> StepOut:
        raise NotImplementedError

    def verify(self, tokens, positions, draft_lens, kpool, vpool,
               block_tables, **kw) -> StepOut:
        raise NotImplementedError

    def decode_with_chunk(self, chunk_tokens, start, block_row, plen,
                          tokens, positions, block_tables, kpool, vpool,
                          **kw):
        """OPTIONAL: `prefill_chunk` and `decode` as ONE step whose
        layers see the chunk's rows and the decode rows together, for a
        model whose weights are worth reading once an iteration and not
        twice. -> (`StepOut` of the chunk: its hidden rows, the pools
        and slot state after BOTH, the decode step's counters; the
        decode rows' hidden). The engine runs it in place of the two
        programs in an iteration of the ahead order that holds a chunk
        and decode lanes. The step takes neither int8 KV scales, adapters
        nor a mesh axis: a spec that offers it refuses them. This base
        offers none."""
        raise NotImplementedError

    @property
    def offers_decode_with_chunk(self):
        return type(self).decode_with_chunk \
            is not ServingSpec.decode_with_chunk
