"""Row blocks of an n x n float32 matrix, a few at a time on threads:
numpy drops the interpreter lock inside the conversions and products,
and no n x n float64 copy is ever made."""
from concurrent.futures import ThreadPoolExecutor

BLOCK = 1024
THREADS = 8


def over_rows(fn, n):
    """[fn(r0, r1) for each row block], computed on THREADS threads."""
    spans = [(r, min(r + BLOCK, n)) for r in range(0, n, BLOCK)]
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        return list(pool.map(lambda s: fn(*s), spans))
