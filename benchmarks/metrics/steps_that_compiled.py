"""How many steps of the whole run, set-up included, made the compiler work
or fetched an executable from the compile cache: `step_dispatch` spans of
the program's tracer with an `xla_compile` span nested in them (an exact
count). 1 is the first step; each one more is a program the warm-up did not
expect. The line before the last names each one's iteration and seconds.
None where the program opens no `step_dispatch` span."""

import json

from deeplearning4j_tpu.observe import get_active_tracer


def read(run):
    tracer = get_active_tracer()
    if tracer is None:
        return None
    spans = {s.span_id: s for s in tracer.recorder.spans()}
    if not any(s.name == "step_dispatch" for s in spans.values()):
        return None
    compiled = {}       # span id of a step_dispatch -> seconds compiling in it
    for span in spans.values():
        if span.name != "xla_compile":
            continue
        above = spans.get(span.parent_id)
        while above is not None and above.name != "step_dispatch":
            above = spans.get(above.parent_id)
        if above is not None:
            compiled[above.span_id] = (compiled.get(above.span_id, 0.0)
                                       + (span.end_ns - span.start_ns) / 1e9)
    print("steps_that_compiled " + json.dumps(
        [{"iteration": spans[i].attrs.get("iteration"),
          "dispatch_s": (spans[i].end_ns - spans[i].start_ns) / 1e9,
          "compile_s": s} for i, s in compiled.items()]), flush=True)
    return len(compiled)
