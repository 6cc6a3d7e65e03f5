"""Mosaic (Pallas) custom calls executed in one step on the first chip:
the operations of the device trace whose HLO text names the target
``tpu_custom_call``.  (``TrainStep.compiled_text()`` would give the static
count, but it compiles the step again: 17 s for BERT-base, a minute for
GPT-2 345M, in every traced run of every later check.)"""


def reduce(trace, run):
    chip = trace.chips[0]
    lo, hi = trace.window[chip]
    calls = sum(1 for name, start, _ in trace.ops[chip]
                if name in trace.mosaic and lo <= start < hi)
    return calls / trace.steps
