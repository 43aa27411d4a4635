"""Remap prologues one application runs: windows whose program begins
with the planner's swaps as one batched exchange, by the program's
counters ``remap.pager.prologues.k<k>`` over the window.  None where
the program keeps no such counter."""

import harness
import roofline_remap


def read(ctx):
    by_k = roofline_remap.prologues_by_k(ctx["window_counters"])
    if not by_k:
        return None
    n = ctx["attempted"]
    harness.say(prologues_an_application_by_pairs={
        f"k{k}": count / n for k, count in sorted(by_k.items())},
        residual_page_permutations=ctx["window_counters"].get(
            "remap.pager.page_perms", 0) / n,
        gates_left_on_paged_qubits=ctx["window_counters"].get(
            "exchange.pager.global_2x2", 0) / n)
    return sum(by_k.values()) / n
