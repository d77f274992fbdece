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
state layers, the third.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["LayerSpec", "StateSpec", "ServingForm"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer's attention, as the engine has to serve it."""

    num_heads: int               # query heads
    num_kv_heads: int            # K/V heads; query head h reads h // ratio
    head_dim: int
    window: Optional[int]        # key j visible to query i iff 0 <= i-j < window
    group: str                   # cache group: layers of one group share
    #                              a pool shape, an id space and a table


@dataclasses.dataclass(frozen=True)
class StateSpec:
    """One layer's fixed-size state a sequence, as the engine has to keep
    it: zeros when the sequence is admitted, carried from one chunk or
    decode step to the next, saved and restored with the sequence's K/V."""

    shape: tuple                 # of ONE sequence's state
    group: str                   # state group: layers of one group share
    #                              a shape, a dtype and a slot a sequence
    dtype: Optional[object] = None   # None: the form's `dtype`


class ServingForm:
    """Base of a model's serving form.  Subclasses set `layer_specs`
    (one `LayerSpec` or `StateSpec` a layer), `vocab_size`,
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
        one.  `valid` [B] bool marks real rows of a padded
        decode batch (None: all).  -> (h, extra, stats or None), `stats`
        an int32 vector along `stat_counters`."""
        raise NotImplementedError

    def logits(self, params, h):
        """h [B, S, H] -> logits [B, S, V] at every position."""
        raise NotImplementedError

    def last_logits(self, params, h):
        """The last position's logits [B, V]."""
        return self.logits(params, h)[:, -1]
