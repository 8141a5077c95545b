"""jit_count_filter_sort_page as q010_count_filter calls it: every
film that has a genre is a candidate (in this graph every film), and
the kernel reads each candidate's uid, its genre count and its rank in
the order key (`name`), and writes one page. Three u32 vectors of the
films' length in, a page out; memory-bound by statement."""

TEMPLATE = "q010_count_filter"


def least_bytes(s: dict) -> float:
    return 4 * (3 * s["films"] + s["params"][TEMPLATE]["first"])
