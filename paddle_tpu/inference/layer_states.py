"""What a model's layers keep for a request between steps, by layer kind.

`ServingEngine` allocates what a model declares here and nothing else:

- attention layers keep PAGES: one `[attention_layers, num_blocks, kv_heads,
  block_size, head_dim]` pair, addressed by a request's block table, growing
  with its length (the engine's page allocator);
- state-space layers keep a ROW SLOT: each `RowState` is a stack
  `[layers, max_batch + 1, *shape]` of fixed size a request, whatever its
  length. A request takes a slot the first time a step schedules it, keeps
  it while it lives and gives it up when it finishes or is pre-empted; the
  last slot is the padding row's. The step zeroes a slot's state ON THE
  DEVICE when the row's chunk starts at position 0 (a new or a re-prefilled
  request), so a slot is never cleared from the host.

`PagedCausalLM` declares attention in every layer and no row state
(`LayerStates.attention_only`); `models/nemotron_h.py` declares one
attention layer of eleven and two row states; `models/minicpm_sala.py`
two attention layers of eight with a page side, and two row states.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

__all__ = ["RowState", "PageSide", "LayerStates"]


@dataclasses.dataclass(frozen=True)
class RowState:
    name: str
    layers: int
    shape: Tuple[int, ...]          # of one row in one layer
    dtype: str


@dataclasses.dataclass(frozen=True)
class PageSide:
    name: str
    layers: int
    shape: Tuple[int, ...]          # of one page's entries
    dtype: str


@dataclasses.dataclass(frozen=True)
class LayerStates:
    attention_layers: int
    kv_heads: int
    head_dim: int
    row_states: Tuple[RowState, ...] = ()
    page_sides: Tuple[PageSide, ...] = ()
    # counters the step returns as one int32 vector, in this order: the
    # model owns the names, the engine adds the counts to the registry
    # where it fetches the step's tokens
    counters: Tuple[str, ...] = ()
    # what the model wants said in the step's span beside the engine's
    # own arguments, known on the host before the step runs:
    # `step_args([(start, chunk), ...]) -> {name: number}` over the
    # scheduled rows (a reader prices a kernel's calls by it)
    step_args: Optional[Callable] = None

    @classmethod
    def attention_only(cls, cfg) -> "LayerStates":
        return cls(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim)
