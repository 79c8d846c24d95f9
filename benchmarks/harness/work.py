"""The work a dispatch needs, and the least time the chip could take.

Reads the roofline table a configuration names
(`benchmarks/rooflines/<name>.json`) and the table of peaks
(`benchmarks/peaks.json`).  A device kind that is not in the table of
peaks is an error, never a default.
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def load_table(name: str) -> dict:
    with open(os.path.join(BENCH, "rooflines", f"{name}.json")) as fh:
        return json.load(fh)


def load_peak(device_kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       "benchmarks/peaks.json")
    return peaks[device_kind]


def fp_muls(table: dict, lanes: int, rows: int, fresh_messages: int
            ) -> float:
    """Base-field multiplications of ONE dispatch with `lanes` live
    signatures folded into `rows` Miller rows, of which
    `fresh_messages` messages have to be hashed to the curve."""
    lane = sum(v["fp_mul"] for v in table["per_lane"].values())
    row = sum(v["fp_mul"] for k, v in table["per_row"].items()
              if k != "hash_to_g2")
    h2c = table["per_row"]["hash_to_g2"]["fp_mul"]
    once = sum(v["fp_mul"] for v in table["per_dispatch"].values())
    return lanes * lane + rows * row + fresh_messages * h2c + once


def least_seconds(table: dict, peak: dict, muls: float) -> float:
    """The least time the chip could take for `muls` multiplications,
    reckoned in int8 multiply-adds against its int8 peak."""
    conv = table["fp_mul_as_int8"]
    ops = muls * conv["macs"] * conv["ops_per_mac"]
    return ops / peak["int8_ops_per_s"]
