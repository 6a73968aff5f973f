"""Host time per step inside the trainer's input call (``data/pipeline.py``
``EpochSharder``: the gather of the step's rows and their transfer), from
the ``bench.input`` spans of the traced window.  Moves tokens_per_s."""


def read(run, red):
    n = red["input"]["n"]
    return red["input"]["ns"] / n / 1e6 if n else None
