"""Operations and bytes one ``spec_scatter_add`` call needs: each request
reads its index and its value row, and reads, adds to and writes back one
table row.

Counted from the call's shapes: ``requests`` rows of ``d`` elements of
``itemsize`` bytes, one add per element.  The aligned block of rows the
kernel moves today is not counted, so a kernel that moves only the row
reads as a gain.
"""


def cost(call):
    """(operations, bytes) of one call."""
    row = call["d"] * call["itemsize"]
    return (float(call["requests"] * call["d"]),
            float(call["requests"] * (3 * row + 4)))
