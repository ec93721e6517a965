"""What a TSBS query logically needs, from shapes alone: the rows its
selector and range cover times the lanes it must read (ts, tsid, value:
8 bytes each), plus the grid it returns (8 bytes a cell); one addition or
comparison a row and one a cell."""

ROW_BYTES = 24  # ts i64, tsid u64, value f64


def per_query(traffic: dict, config: dict) -> dict:
    hosts = config["hosts"] if int(traffic["hosts_per_query"]) == 0 else int(traffic["hosts_per_query"])
    rows = hosts * int(traffic["range_s"]) // int(config["log_interval_s"])
    out_rows = 1 if traffic.get("across") else hosts
    cells = out_rows * (int(traffic["range_s"]) // int(traffic["step_s"]))
    return {"rows": rows, "cells": cells,
            "bytes": rows * ROW_BYTES + cells * 8, "flops": rows + cells}


def logical(traffic: dict, config: dict, counts: dict) -> dict:
    one = per_query(traffic, config)
    n = counts["operations"]
    return {"bytes": n * one["bytes"], "flops": n * one["flops"]}
