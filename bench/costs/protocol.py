"""Least work of one protocol round and of one CGC aggregation, in fp32.

Whatever way a program computes the projections, a correct round must
read the (n, d) table of the workers' gradients once and read and write
w once; the CGC aggregation must read the table once and write the
d-vector once. Its operations are the norms and the scaled sum, 2nd
each; the slot loop's projections are not counted, since their number
depends on how many raws enter the reference set.
"""

F32 = 4


def cgc_flops(n: int, d: int) -> float:
    return 4.0 * n * d


def cgc_bytes(n: int, d: int) -> float:
    return F32 * (n * d + d)


def round_flops(n: int, d: int) -> float:
    return cgc_flops(n, d)


def round_bytes(n: int, d: int) -> float:
    return F32 * (n * d + 2 * d)
