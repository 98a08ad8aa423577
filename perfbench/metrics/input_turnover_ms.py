"""input_turnover_ms: closing one epoch's pipeline, opening the next and
waiting for its first, cold batch; over all the stretch's rounds."""

from perfbench.harness import span_reader


def read(ctx):
    return span_reader.per_round_ms(
        ctx, ("pipeline_close", "pipeline_open", "data_wait"),
        where=lambda span, found: (span["name"] != "data_wait"
                                   or span["round"] in found["epoch_first"]))
