"""Operations and least bytes of one ``qconv_dw_kernel`` call, from its
shapes: the first ``kh`` operands are views of one padded activation array
(one per window row, counted once in the bytes), the result holds every
output element of every channel, and the window is square, so
2 * |result| * kh * kh operations; every other operand read once, the
result written once."""
from math import prod


def _views(operands) -> int:
    """How many leading operands are the same buffer (a depthwise call's
    window-row views of one activation array)."""
    n = 1
    while n < len(operands) and operands[n] == operands[0]:
        n += 1
    return n


def count(operands, result):
    kh = _views(operands)
    nbytes = sum(prod(d) * b for d, b in operands[kh - 1:])
    nbytes += sum(prod(d) * b for d, b in result)
    return 2 * prod(result[0][0]) * kh * kh, nbytes
