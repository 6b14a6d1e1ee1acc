"""Count cross-process collectives in the world=2 compiled step (round 5).

Closes the round-4 scaling-table footnote (BASELINE.md "Reading the
table honestly" §2): resnet20_cifar pays 385 ms of boundary cost at
world=2 where bert_tiny pays 55 ms despite shipping ~16x MORE gradient
bytes — asserted to be "the compiled conv graph itself, not the
gradient tree; not attributed further on this box".  This script lowers
the SAME explicit-psum train step both scaling-table members run, for a
size-2 data mesh, and counts the collective ops in the optimized HLO.
A 2-virtual-device single-process mesh compiles the identical program
the two-process world=2 run executes (same mesh shape, same partitioner
input), so the crossing counts need no hardware and no second process.

Round 6: the counting moved into ``tpu_hc_bench.analysis.hlo`` and got
correct (ADVICE r5): the old whole-text regex also matched operand
references (every consumer of %all-reduce.N re-mentions the name) and
the ``-done`` halves of async pairs, inflating absolute counts; the
parser counts *definition sites* only and folds ``-start``/``-done``
into one op.  This script is now a thin wrapper — the same counts for
any member come from::

    JAX_PLATFORMS=cpu python -m tpu_hc_bench.analysis --model <name>

Usage: JAX_PLATFORMS=cpu python scripts/exp_hlo_collectives_r05.py
"""

from __future__ import annotations

import sys

sys.path.insert(0, ".")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from tpu_hc_bench.analysis import hlo  # noqa: E402


def count_collectives(model_name: str, batch: int) -> dict[str, int]:
    text = hlo.lower_world_step_hlo(model_name, batch=batch, world=2)
    return hlo.collective_counts(text)


def main() -> int:
    # the literal scaling-table members at their scaling-table batches
    # (scripts/scaling_table.py: resnet20_cifar bs=64, bert_tiny bs=32)
    for name, bs in (("resnet20_cifar", 64), ("bert_tiny", 32)):
        counts = count_collectives(name, bs)
        total = sum(counts.values())
        print(f"{name} bs={bs} world=2 optimized-HLO collectives "
              f"(definition sites, async pairs folded): {total}  {counts}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
