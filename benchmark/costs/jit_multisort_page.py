"""jit_multisort_page as q058_after_with_sort calls it: every rated
film is a candidate (in this graph every film); the kernel reads each
candidate's uid and its rank in the order key (`rating`) and writes
one page. Two u32 vectors of the films' length in, a page out;
memory-bound by statement."""

TEMPLATE = "q058_after_with_sort"


def least_bytes(s: dict) -> float:
    return 4 * (2 * s["films"] + s["params"][TEMPLATE]["first"])
