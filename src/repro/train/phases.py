"""Two-phase BERT pretraining schedule (paper §3.3, Table 6).

Phase 1: seq 128, 20 predictions, 90% of steps (paper: 36/40 epochs).
Phase 2: seq 512, 80 predictions, 10% of steps (paper: 4/40 epochs).
"""
from __future__ import annotations

import dataclasses
from typing import List

from repro.configs.base import InputShape
from repro.models.api import mlm_positions_count


@dataclasses.dataclass(frozen=True)
class Phase:
    name: str
    seq_len: int
    n_predictions: int
    global_batch: int          # paper Table 6: 4096 / 2048 sentences
    steps: int
    learning_rate: float = 1e-4

    @property
    def shape(self) -> InputShape:
        return InputShape(self.name, self.seq_len, self.global_batch,
                          "train")


def bert_phases(phase1_steps: int, phase2_steps: int, *,
                global_batch_p1: int = 4096, global_batch_p2: int = 2048,
                learning_rate: float = 1e-4) -> List[Phase]:
    """The two phases, each with its own step count (the paper splits its
    epochs 36/4, i.e. 90%/10% of the steps)."""
    return [
        Phase("phase1", 128, mlm_positions_count(128), global_batch_p1,
              phase1_steps, learning_rate),
        Phase("phase2", 512, mlm_positions_count(512), global_batch_p2,
              phase2_steps, learning_rate),
    ]
