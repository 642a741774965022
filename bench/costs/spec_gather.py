"""Operations and bytes one ``spec_gather`` call needs: each request
reads its index and one table row, and writes one output row.

Counted from the call's shapes: ``requests`` rows of ``d`` elements of
``itemsize`` bytes.  The aligned block of rows the kernel moves today is
not counted, so a kernel that moves only the row reads as a gain.
"""


def cost(call):
    """(operations, bytes) of one call."""
    row = call["d"] * call["itemsize"]
    return 0.0, float(call["requests"] * (2 * row + 4))
