"""moe_rows_held_share (%), read from program_counter.

The program's gauge ``moe/rows_held_share`` of the LAST WARM-UP STEP, as a
percentage: the routed rows the expert layer computed HERE (those whose
expert this rank holds) over all ``T x k`` the router assigned, averaged
over the layers; 100 / expert_parallel_size for a uniform router (6.25 at
16 ranks). Folded and read as ``moe_rows_max_over_mean`` is (the family's
``program_gauges``: the same step of every run). It says how much expert
work the window starts from, and it is what ``moe_gmm_roofline`` cannot
see: that reader is handed the family's EXPECTED row count
(``moe_gmm_flops_per_step``, 1 / expert_parallel_size of the rows) and
nothing of the run, so where the router drifts to send r times its share
here the roofline reads r times too high — divide it by this metric over
its expected value before believing it, and never read it over 100 %
without looking here. None where the program sets no such gauge (a layer
that holds all its experts).
"""

NAME = "moe_rows_held_share"
UNIT = "%"
LAYER = "expert layer"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(record):
    gauges = getattr(record.family, "program_gauges", None)
    share = gauges().get("moe/rows_held_share") if gauges else None
    return None if share is None else 100.0 * share
