"""What `serving.LLMEngine` asks of a model (ROADMAP D2): the model hands
the engine its serving form, and the engine names no model.

A form gives the engine the parameter arrays, the embedding, one step per
layer and the head, all at array level so they trace into the engine's
jitted programs; and per layer a `LayerSpec` from which the engine builds
the attention that step calls (flash over the chunk for a whole-prompt
prefill, the ragged kernel or the paged fallback against the pools
otherwise) and the cache group the layer's K/V live in.

`GPTForCausalLM.serving_form()` (models/gpt.py) is the first form,
`AfmoeForCausalLM.serving_form()` (models/afmoe.py) the second.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

__all__ = ["LayerSpec", "ServingForm"]


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer's attention, as the engine has to serve it."""

    num_heads: int               # query heads
    num_kv_heads: int            # K/V heads; query head h reads h // ratio
    head_dim: int
    window: Optional[int]        # key j visible to query i iff 0 <= i-j < window
    group: str                   # cache group: layers of one group share
    #                              a pool shape, an id space and a table


class ServingForm:
    """Base of a model's serving form.  Subclasses set `layer_specs`
    (one `LayerSpec` a layer), `vocab_size`, `max_position_embeddings`
    and `dtype`, and implement the four array-level functions."""

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
        back untouched.  `valid` [B] bool marks real rows of a padded
        decode batch (None: all).  -> (h, extra, stats or None), `stats`
        an int32 vector along `stat_counters`."""
        raise NotImplementedError

    def logits(self, params, h):
        """h [B, S, H] -> logits [B, S, V] at every position."""
        raise NotImplementedError

    def last_logits(self, params, h):
        """The last position's logits [B, V]."""
        return self.logits(params, h)[:, -1]
