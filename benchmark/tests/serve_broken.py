"""A launcher with the timed path broken underneath, for the test that has
to see ``correct`` come out false. ``BENCH_BREAK`` chooses the fault:

- ``fold``: the fold of the measured round's first batch returns its
  accumulator unchanged (a step that returns its state unchanged);
- ``answer``: one element of the decoded global model is altered where it is
  produced.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    fault = os.environ["BENCH_BREAK"]
    if fault == "fold":
        from xaynet_tpu.ops import fold_pallas

        real, calls = fold_pallas.fold_planar_batch_pallas, [0]

        def fold_once_skipped(acc, stack, order, **kwargs):
            calls[0] += 1
            if calls[0] == 2:  # 1 = the warm-up round's batch
                return acc
            return real(acc, stack, order, **kwargs)

        fold_pallas.fold_planar_batch_pallas = fold_once_skipped
    elif fault == "answer":
        from xaynet_tpu.core.mask import encode

        real_decode = encode.decode_vect_fast

        def decode_altered(*args, **kwargs):
            out = real_decode(*args, **kwargs)
            out[len(out) // 2] += 2.0 ** -20
            return out

        encode.decode_vect_fast = decode_altered
    else:
        raise SystemExit(f"unknown BENCH_BREAK {fault!r}")
    from xaynet_tpu.server import runner

    runner.main()
