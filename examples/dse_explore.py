"""DSE walkthrough (paper Fig 15): sweep die groupings × quantization for a
model, print the latency heatmap with OOM blanks, and show how the winner
reconfigures the Track-B serving engine.

    PYTHONPATH=src python examples/dse_explore.py [arch]
"""
import math
import sys

from repro.configs import get_config
from repro.core import dse


def main():
    arch = sys.argv[1] if len(sys.argv) > 1 else "llama3.1-70b"
    cfg = get_config(arch)
    seqs = [1_000, 5_000, 10_000, 50_000, 100_000]
    print(f"=== DSE heatmap: {arch}, 8 IFC dies, W4A16 "
          f"(ms/token; -- = OOM) ===")
    grid = dse.heatmap(cfg, seqs, total_dies=8, wbits=4, abits=16)
    header = "config".ljust(18) + "".join(f"{s:>10}" for s in seqs)
    print(header)
    for name, row in grid.items():
        cells = "".join(
            f"{'--':>10}" if math.isinf(row[s]) else f"{row[s]*1e3:10.1f}"
            for s in seqs)
        print(name.ljust(18) + cells)
    for seq in (1_000, 100_000):
        best = dse.best_config(cfg, seq, 8, 4, 16)
        print(f"best @ {seq}: {best.system}  "
              f"({best.latency * 1e3:.1f} ms/token)")
    print("\n=== engine reconfiguration (paper: software-defined) ===")
    for seq in (1_000, 100_000):
        eng = dse.recommend_engine_config(arch, seq)
        point = dse.recommend_point(arch, seq)
        system = point.system if point else "no fit"
        print(f"ctx {seq:>7}: system={system:18s} quant={eng.quant} "
              f"kv_quant={eng.kv_quant} partitions={eng.attn_partitions}")
    t = dse.takeaways(get_config("opt-30b"), get_config("llama3.1-70b"))
    print("\npaper takeaways reproduced:", t)


if __name__ == "__main__":
    main()
