"""Bytes a sample scan must read, fixed by the answer and the schema.

An answer served from samples at resolution K used the family's first
`rows_read` rows. Whatever implements the scan must read, for each of
them, the aggregated value (a float32), the row's stratum id (for its
Horvitz-Thompson weight) and every predicate and group column, each at
the least width its cardinality or type allows. Membership in the K
prefix is the row's position, so the per-row priority need not be read.
Widths come from the configuration's schema, never from the program's
storage types, so the count stays put whatever layout a program picks.
"""
from __future__ import annotations

import math

F32_BITS = 32


def column_bits(schema: dict, name: str) -> int:
    """Least bits per row of a column: ceil(log2(cardinality)) for a
    categorical one, 32 for a float32 measure."""
    col = schema[name]
    if col["kind"] == "categorical":
        card = len(col["values"]) if "values" in col else col["cardinality"]
        return math.ceil(math.log2(card)) if card > 1 else 0
    return F32_BITS


def stratum_bits(schema: dict, phi) -> int:
    """Least bits of a stratum id of family phi: its strata are at most the
    product of its columns' cardinalities."""
    n = 1
    for c in phi:
        col = schema[c]
        n *= len(col["values"]) if "values" in col else col["cardinality"]
    return math.ceil(math.log2(n)) if n > 1 else 0


def required_bytes(schema: dict, spec, phi, rows_read: int) -> float:
    """Bytes one sampled answer had to read: rows x (value + stratum id +
    predicate and group columns) at their least widths."""
    bits = stratum_bits(schema, phi)
    if spec.value_column:
        bits += F32_BITS
    bits += sum(column_bits(schema, c) for c in spec.columns_read
                if c != spec.value_column)
    return rows_read * bits / 8.0
