"""Paper Table 6: two-phase pretraining configuration + epoch-time model."""
from __future__ import annotations

from benchmarks.common import PAPER, csv
from repro.train.phases import bert_phases


def main():
    phases = bert_phases(900, 100)
    for ph in phases:
        csv(f"table6/{ph.name}", 0.0,
            f"seq={ph.seq_len} predictions={ph.n_predictions} "
            f"global_batch={ph.global_batch} lr={ph.learning_rate}")
    # paper epoch times: 6h (phase1) / 16h (phase2) on 256 T4s
    tps_cluster = PAPER["t4_tokens_per_s"] * 256 * 0.70
    epoch_h_p1 = PAPER["tokens_per_epoch"] / tps_cluster / 3600.0
    # phase 2: seq 512 -> ~4x tokens per sample at ~0.6x throughput/token
    epoch_h_p2 = 4 * PAPER["tokens_per_epoch"] / (tps_cluster * 0.6) / 3600.0
    csv("table6/model_epoch_time_p1", 0.0,
        f"hours={epoch_h_p1:.1f} (paper: 6h)")
    csv("table6/model_epoch_time_p2", 0.0,
        f"hours={epoch_h_p2:.1f} (paper: 16h)")
    total_days = (36 * epoch_h_p1 + 4 * epoch_h_p2) / 24.0
    csv("table6/model_total", 0.0, f"days={total_days:.1f} (paper: 12)")


if __name__ == "__main__":
    main()
