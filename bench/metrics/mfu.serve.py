"""Share (%) of the chip's bf16 peak that the model work of the requests
finished reaches over the device time of the serving programs (decode
step and prefill chunks) from the window's opening to the end of the
drain.  Operations from shapes (``bench/work.py``): weights and attention
over each token's real context."""


def read(r):
    if r.trace is None or not r.facts.get("flops"):
        return None
    names = (r.facts["decode_program"], r.facts["prefill_program"])
    span = r.trace.window(r.facts["served_span"])
    secs = r.trace.module_seconds(span, lambda n: any(p in n for p in names))
    return 100.0 * r.facts["flops"] / (secs * r.peaks["bf16_flops_per_s"]) if secs > 0 else None
