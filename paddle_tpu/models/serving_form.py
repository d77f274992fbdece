"""What `serving.LLMEngine` asks of a model (ROADMAP D2): the model hands
the engine its serving form, and the engine names no model.

A form gives the engine the parameter arrays, the embedding, one step per
layer and the head, all at array level so they trace into the engine's
jitted programs; and per layer what the engine has to keep for it between
steps.  A `LayerSpec` names an attention layer: from it the engine builds
the attention that step calls (flash over the chunk for a whole-prompt
prefill, the ragged kernel or the paged fallback against the pools
otherwise) and the cache group the layer's K/V live in.  A `StateSpec`
names a layer whose memory of a sequence is one tensor of fixed size
whatever the sequence's length (a short convolution's last inputs, a
recurrence's state): the engine keeps it in a slot a sequence of a state
group (`serving.kv_cache.StateCache`) and hands the layer step its rows.

`GPTForCausalLM.serving_form()` (models/gpt.py) is the first form,
`AfmoeForCausalLM.serving_form()` (models/afmoe.py) the second,
`Lfm2MoeForCausalLM.serving_form()` (models/lfm2.py), the first with
state layers, the third, `Mistral4ForCausalLM.serving_form()`
(models/mistral4.py), the first with latent layers, the fourth,
`BrumbyForCausalLM.serving_form()` (models/brumby.py), the first with
NO attention layer at all - every layer a `StateSpec`, its state updated
in place in its slot - the fifth, `KeyeForCausalLM.serving_form()`
(models/keye.py), the first with sparse layers, the sixth, and
`FalconH1ForCausalLM.serving_form()` (models/falcon_h1.py), the first
whose every layer keeps THREE memories side by side - its attention's K/V,
a convolution's last inputs and a Mamba-2 state updated in place - the
seventh.

A layer names one spec, or a TUPLE of them when it keeps several memories
side by side (an attention and a state-space mixer that read the same
normed input in one block): the engine keeps each in its own group as if
it were a layer of its own, and hands the layer step one function a spec,
in the tuple's order.  Such a layer cannot be split into two: the second
branch reads the residual as it stood before the first wrote to it.

A `LatentSpec` names an attention layer whose cache keeps ONE row a token,
a latent from which every head's key and value are made (multi-head latent
attention): what such a layer ATTENDS in a whole-prompt prefill, per-head
keys and values expanded from the latents of the chunk itself, is not what
it STORES, and every program that reads the pool attends the stored rows
in the absorbed form, the expansion folded into the query and out of the
result.  The layer hands the engine both (`ServingForm.layer`).

A `SparseSpec` names a GQA layer whose every query attends only the
`topk` earlier tokens a learned INDEXER scores highest (DeepSeek-V3.2's
sparse attention): beside a token's K and V its cache keeps one index key
(`index_dim` lanes, every index head scores against it), in a third pool
of the layer's group under the same block table; the engine stores all
three and calls the kernels of `ops.sparse_attention`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["LayerSpec", "LatentSpec", "SparseSpec", "StateSpec",
           "ServingForm"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer's attention, as the engine has to serve it."""

    num_heads: int               # query heads
    num_kv_heads: int            # K/V heads; query head h reads h // ratio
    head_dim: int
    window: Optional[int]        # key j visible to query i iff 0 <= i-j < window
    group: str                   # cache group: layers of one group share
    #                              a pool shape, an id space and a table

    latent = False               # the cache keeps K and V as attended
    sparse = False               # every visible key is attended
    index_lanes = 0              # no index key a token

    def pool_row(self) -> tuple:
        """(heads, lanes a head, pools a layer) of the group's cache."""
        return self.num_kv_heads, self.head_dim, 2

    @property
    def scope(self) -> str:
        """The named scope of the layer's attention in a program."""
        return "attn/window" if self.window else "attn/full"


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """One layer's latent attention, as the engine has to serve it: the
    cache row of a token is `key_dim` numbers that every query head scores
    against (one K/V "head" under `num_heads` query heads), and the row's
    first `value_dim` are the value every head sums."""

    num_heads: int               # query heads
    key_dim: int                 # a cache row: what an absorbed query meets
    value_dim: int               # its leading lanes that are the value
    scale: float                 # the softmax's, in both forms
    group: str = "latent"        # cache group: ONE pool a layer

    latent = True                # the engine stores `rows`, not K and V
    sparse = False
    index_lanes = 0
    window = None                # every stored row is visible
    scope = "attn/latent"

    def pool_row(self) -> tuple:
        """One "head" a token, the row in whole lane tiles, ONE pool."""
        from ..ops.paged_attention import latent_pool_lanes

        return 1, latent_pool_lanes(self.key_dim), 1


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """One layer's learned sparse attention, as the engine has to serve
    it: GQA as `LayerSpec` over the `topk` keys the indexer picks for each
    query, the indexer's `index_heads` heads scoring against ONE index key
    of `index_dim` lanes a token."""

    num_heads: int               # query heads
    num_kv_heads: int            # K/V heads; query head h reads h // ratio
    head_dim: int
    index_heads: int
    index_dim: int               # an index key's published lanes
    topk: int                    # keys a query attends
    scale: float                 # the softmax's
    group: str = "sparse"

    latent = False
    sparse = True                # the engine calls `ops.sparse_attention`
    window = None
    scope = "attn/sparse"

    @property
    def index_lanes(self) -> int:
        """An index key's lanes as the pool keeps it: whole lane tiles."""
        from ..ops.sparse_attention import index_pool_lanes

        return index_pool_lanes(self.index_dim)

    def pool_row(self) -> tuple:
        """K and V as `LayerSpec`'s; the index pool is `index_lanes`."""
        return self.num_kv_heads, self.head_dim, 2


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """One layer's fixed-size state a sequence, as the engine has to keep
    it: zeros when the sequence is admitted, carried from one chunk or
    decode step to the next, saved and restored with the sequence's K/V."""

    shape: tuple                 # of ONE sequence's state
    group: str                   # state group: layers of one group share
    #                              a shape, a dtype and a slot a sequence
    dtype: Optional[object] = None   # None: the form's `dtype`
    # False: the layer's step takes the rows' states, gathered, and
    # returns the new ones, which the engine scatters back (a state of
    # kilobytes).  True: it takes the layer's POOL and the rows' slot
    # indices and returns the pool, updated in those slots alone: a state
    # of megabytes is then moved once in and once out, by the layer's own
    # kernel, and no program holds a gathered copy of it
    in_place: bool = False


class ServingForm:
    """Base of a model's serving form.  Subclasses set `layer_specs`
    (a spec a layer, or a tuple of specs), `vocab_size`,
    `max_position_embeddings` and `dtype`, and implement the four
    array-level functions."""

    # what the entries of `layer`'s int32 counts are: (counter name,
    # labels) each; the engine sums the vectors over layers, reads them
    # back with the step's tokens and adds them to those counters
    stat_counters: tuple = ()
    # `EngineConfig` options the family does not carry: the engine raises
    # at construction, naming the option, when one is switched on
    unsupported: tuple = ()

    def params(self) -> dict:
        """name -> array, read fresh every step."""
        raise NotImplementedError

    def embed(self, params, ids, pos):
        """ids [B, S], pos [B, S] (or [S], the same for every row) int32
        -> hidden states [B, S, H]."""
        raise NotImplementedError

    def layer(self, l, params, h, pos, attn_fn, valid=None):
        """Layer `l` over h [B, S, H] at absolute positions `pos` ([B, S]
        or [S]).
        `attn_fn(q [B,S,Hq,D], k, v [B,S,Hkv,D]) -> (o [B,S,Hq,D],
        extra)` is the engine's; `extra` (the updated pools) is handed
        back untouched.  For a `StateSpec` layer the engine passes in its
        place `state_fn(step) -> (y, extra)`: `step(state [B, *shape]) ->
        (y, new state)` is the layer's own, called once with the rows'
        states as they stood before this chunk (zeros at a sequence's
        start), and what it returns as the new state is kept for the next
        one.  For a `StateSpec` with `in_place` the step is `step(pool
        [slots + 1, *shape], rows [B] int32, fresh) -> (y, pool)`: the
        layer's pool as it is, the rows' slot indices (padding rows of a
        decode batch name the last slot, which no sequence holds) and
        whether the rows start from no history (a whole-prompt prefill:
        the slots' contents are then not to be read); it hands back the
        pool, updated in the rows' slots.  For a `LatentSpec` layer it
        passes `latent_fn(rows [B,S,
        key_dim], whole, stored) -> (o, extra)`: `rows` are the chunk's
        latents, which the engine stores; `whole(attend) -> o` is called
        in a whole-prompt prefill with `attend(q, k, v [B,S,H,D]) -> [B,S,
        H,D]`, causal attention within the chunk at the spec's scale;
        `stored(attend) -> o` in every program that reads the pool, with
        `attend(q [B,S,H,key_dim]) -> [B,S,H,value_dim]`, every head
        against the stored rows.  The engine calls exactly one of the
        two.  For a `SparseSpec` layer it passes `sparse_fn(q, k, v [as
        attn_fn's], qi [B,S,index_heads,index_dim], ki [B,S,index_dim],
        w [B,S,index_heads] float32) -> (o, extra)`: the engine stores k,
        v and the index keys ki, and attends each query's `topk` keys of
        largest `sum_j w_j relu(qi_j . ki)`.  A layer whose spec is a
        tuple takes one such function a spec in its place, in the tuple's
        order (`layer(l, params, h, pos, fn_1, .., fn_n, valid=None)`),
        and returns as `extra` what each hands back, concatenated in that
        order.  `valid` [B] bool marks real
        rows of a padded decode batch, or [B, S] real positions where a
        prompt carries decode rows (a form whose every layer is a
        `LayerSpec`; None: all).  -> (h, extra, stats or None), `stats`
        an int32 vector along `stat_counters`."""
        raise NotImplementedError

    def logits(self, params, h):
        """h [B, S, H] -> logits [B, S, V] at every position."""
        raise NotImplementedError

    def last_logits(self, params, h):
        """The last position's logits [B, V]."""
        return self.logits(params, h)[:, -1]
