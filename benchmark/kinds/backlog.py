"""Traffic kind ``backlog``: every request queued before the window opens, a
closed system; the tokens completed per second are what is judged."""

from ._serving import serve


def run(cell):
    return serve(cell, open_loop=False)
