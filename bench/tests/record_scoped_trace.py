"""Record the small chip trace with the program's own names.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>

Run on one TPU chip.  Records what ``record_trace.py`` records (two
8-sweep ``pallas`` solves at 1024² in ``bench.solve`` spans, a 20 ms
``bench.host`` sleep between them), now with the program's
``repro.solve`` spans and device scopes in it, and writes beside it
``<out stem>.hlo.txt``, the text of the executable the solves ran (its
source paths relative to the checkout), and ``<out stem>.scopes.json``,
its ``{instruction name: scope}`` map (``spans.op_scopes``).
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(out: str) -> None:
    sys.path.insert(0, HERE)
    import record_trace

    record_trace.main(out)
    from repro.core import spans
    from repro.kernels import ops

    args, kwargs = ops.jacobi_solve.signature
    text = ops.jacobi_solve.lower(*args, **kwargs).compile().as_text()
    text = text.replace(ROOT + os.sep, "")
    stem = out[:-len(".xplane.pb")]
    with open(stem + ".hlo.txt", "w") as fh:
        fh.write(text)
    scopes = spans.op_scopes(text)
    with open(stem + ".scopes.json", "w") as fh:
        json.dump(scopes, fh, indent=1, sort_keys=True)
    print(stem, len(text), len(scopes))


if __name__ == "__main__":
    main(sys.argv[1])
