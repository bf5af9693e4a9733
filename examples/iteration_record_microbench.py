"""What the serving iteration's record costs the host, in ns an iteration.

    python examples/iteration_record_microbench.py [--iterations 100000]

``observability/spans.py`` keeps one row for every ``ServingEngine.step()``,
spans on or off (``Iteration.open`` / ``close`` / ``write``): four clock
reads, the brackets around the waits on the device and one row assignment.
This times a loop of no-op iterations on the host it runs on (the record
touches no device; run it on the chip's machine for that host's number) and
the parts by themselves: the two clocks, a wait's bracket, the row's
assignment, and, since the collector's passes are timed from ``gc.callbacks``
with JAX's own callback inside them, a pass of each generation over nothing
and JAX's ``collect_garbage()`` alone. One JSON line.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.observability import spans


def ns_a_call(fn, n: int) -> float:
    """Of ``fn()`` in a loop of ``n``, less the loop's own turn."""
    t = time.perf_counter()
    for _ in range(n):
        pass
    empty = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t - empty) / n * 1e9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iterations", type=int, default=100_000)
    n = ap.parse_args().iterations
    row = spans.Iteration()

    def iteration():
        row.open(7, 40, 1000)
        row.stepped, row.ahead, row.slots, row.read_step = 1, 1, 48, 1
        row.close()
        row.write(40, 48, 100, 1048)

    def stamps():
        row.open(7, 40, 1000)
        row.close()

    written = spans._rows_written
    out = {
        "iterations": n,
        "record_ns": ns_a_call(iteration, n),
        "of_it_open_and_close_ns": ns_a_call(stamps, n),
        "of_it_write_ns": ns_a_call(lambda: row.write(40, 48, 100, 1048), n),
        "perf_counter_ns": ns_a_call(time.perf_counter, n),
        "thread_time_ns": ns_a_call(time.thread_time, n),
        "wait_bracket_ns": ns_a_call(lambda: row.wait(id, None), n)
        - ns_a_call(lambda: id(None), n),
        "rows_written": spans._rows_written - written,
    }
    # the collector: a pass over nothing new, with every callback in it
    # (the seam's two stamps and JAX's collect_garbage() at both ends)
    gc.collect()
    for gen in (0, 1):
        out[f"gc_pass_gen{gen}_us"] = ns_a_call(
            lambda: gc.collect(gen), 2000) / 1e3
    out["gc_pass_gen2_ms"] = ns_a_call(lambda: gc.collect(2), 20) / 1e6
    was = list(gc.callbacks)
    for name, kept in (
            ("others_only", [cb for cb in was
                             if not getattr(cb, "of_the_seam", False)]),
            ("no_callbacks", [])):
        gc.callbacks[:] = kept
        out[f"gc_pass_gen0_{name}_us"] = ns_a_call(
            lambda: gc.collect(0), 2000) / 1e3
    gc.callbacks[:] = was
    try:
        from jax._src.lib import xla_client

        out["jax_collect_garbage_us"] = ns_a_call(
            xla_client._xla.collect_garbage, 2000) / 1e3
    except (ImportError, AttributeError) as e:
        out["jax_collect_garbage_us"] = f"not found: {e!r}"
    out["gc_callbacks"] = [getattr(cb, "__name__", repr(cb))
                           for cb in gc.callbacks]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
