"""What the write path logically needs, from counts alone: each
acknowledged row's key and value lanes (ts, tsid, value: 24 bytes) read
and written once by its flush, and the stored bytes of every SST a
compaction took in read and written once more (the program counts a
compaction's input in stored bytes, not rows: compressed, so below the
lanes' own size and never above); a sort's comparisons (log2 of the
request's rows, a row) as the operations."""

import math

ROW_BYTES = 24


def logical(traffic: dict, config: dict, counts: dict) -> dict:
    rows = counts.get("samples", 0)
    merged = counts.get("merged_sst_bytes", 0.0)
    per_send = int(traffic["samples_per_send"])
    return {"bytes": 2 * ROW_BYTES * rows + 2 * merged,
            "flops": rows * math.log2(max(per_send, 2)) + merged / ROW_BYTES}
