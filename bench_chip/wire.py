"""Prometheus remote-write 1.0 wire bytes, written by hand.

remote.proto: WriteRequest.timeseries = 1; TimeSeries.labels = 1,
.samples = 2; Label.name = 1, .value = 2; Sample.value = 1 (double),
.timestamp = 2 (int64 ms). A `Template` holds the bytes of one request
shape (a list of series, n samples each) built once; `fill` overwrites
the sample values and timestamps in place, so a request costs one numpy
scatter and one snappy pass. Copied in idea from chip_smoke.py's
RequestTemplate (one sample a series), generalised to n samples.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

HEADERS = {"Content-Encoding": "snappy", "Content-Type": "application/x-protobuf"}
# every timestamp of every fleet is a millisecond count of 6 varint bytes
# (2**35 <= ts < 2**42: the years 1971 to 2109)
TS_WIDTH = 6
_SNAPPY = pa.Codec("snappy")


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def label(name: str, value: str) -> bytes:
    n, v = name.encode(), value.encode()
    msg = b"\x0a" + varint(len(n)) + n + b"\x12" + varint(len(v)) + v
    return b"\x0a" + varint(len(msg)) + msg  # TimeSeries.labels = 1


def series_labels(labels: dict[str, str]) -> bytes:
    """The label block of one series, names sorted as Prometheus sends them."""
    return b"".join(label(n, v) for n, v in sorted(labels.items()))


def ts_varints(ts_ms: np.ndarray) -> np.ndarray:
    """[n] int64 -> [n, TS_WIDTH] uint8 varint bytes."""
    ts = np.asarray(ts_ms, dtype=np.int64)
    if ts.size and (ts.min() < 1 << 35 or ts.max() >= 1 << 42):
        raise ValueError("a timestamp does not take 6 varint bytes")
    shifts = 7 * np.arange(TS_WIDTH, dtype=np.int64)
    out = ((ts[:, None] >> shifts) & 0x7F).astype(np.uint8)
    out[:, :-1] |= 0x80
    return out


class Template:
    """One request shape: `blocks` (the encoded label block of each series)
    with `samples` samples each."""

    def __init__(self, blocks: list[bytes], samples: int):
        sample_len = 1 + 8 + 1 + TS_WIDTH
        sample_field = 1 + 1 + sample_len  # tag, length, message
        one = b"\x12" + bytes([sample_len]) + b"\x09" + bytes(8) + b"\x10" + bytes(TS_WIDTH)
        buf = bytearray()
        first = []  # offset of each series' first sample field
        for block in blocks:
            buf += b"\x0a" + varint(len(block) + samples * sample_field) + block
            first.append(len(buf))
            buf += one * samples
        self.series, self.samples = len(blocks), samples
        self._buf = np.frombuffer(buf, dtype=np.uint8)
        at = (np.asarray(first, dtype=np.int64)[:, None]
              + sample_field * np.arange(samples, dtype=np.int64)).reshape(-1)
        self._val_idx = (at + 3)[:, None] + np.arange(8)
        self._ts_idx = (at + 12)[:, None] + np.arange(TS_WIDTH)

    def fill(self, values: np.ndarray, ts_ms: np.ndarray) -> bytes:
        """values[series, samples] (float64) and ts_ms[samples] -> the
        uncompressed WriteRequest."""
        v = np.ascontiguousarray(values, dtype="<f8").reshape(self.series * self.samples)
        self._buf[self._val_idx] = v.view(np.uint8).reshape(-1, 8)
        enc = ts_varints(ts_ms)
        self._buf[self._ts_idx] = np.broadcast_to(
            enc, (self.series, self.samples, TS_WIDTH)).reshape(-1, TS_WIDTH)
        return self._buf.tobytes()


def compress(raw: bytes) -> bytes:
    return _SNAPPY.compress(raw, asbytes=True)
