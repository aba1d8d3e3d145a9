"""Serve engine: median over requests of ``Request.t_first_chunk -
t_admit``: from the slot to the request's turn for a first chunk (entry of
``_prefill_one_chunk``, before any wait for pages): the rest of the
iteration that admitted it, and every iteration it was passed over in
(``prefill_turns_missed_share``).  With ``ttft_chunks_p50_ms`` and
``ttft_backlog_p50_ms`` it is ``ttft_prefill_p50_ms``' difference, request
by request.  (``lib/request_spans.stamp_parts``.)"""

from benchmarks.lib.request_spans import stamp_part_p50_ms


def read(ctx):
    return stamp_part_p50_ms(ctx, "chunk_wait")
