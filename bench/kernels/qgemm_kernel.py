"""Operations and least bytes of one ``qgemm_kernel`` call, from its shapes:
operand 0 is the (M, K) activation tile and the result (M, N), so 2*M*K*N
operations; every operand buffer read once, the result written once."""
from math import prod


def count(operands, result):
    (m, k), (_, n) = operands[0][0], result[0][0]
    nbytes = sum(prod(d) * b for d, b in operands)
    nbytes += sum(prod(d) * b for d, b in result)
    return 2 * m * k * n, nbytes
