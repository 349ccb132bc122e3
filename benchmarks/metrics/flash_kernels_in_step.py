"""How many attention kernel calls the compiled train step holds (an exact
count from its HLO: custom calls to `tpu_custom_call` under the kernels'
names, the stock flash kernels' or the splash kernels'). A metric and not a
correctness condition: where the gate sits is the program's choice."""

import re

from benchmarks.harness import kernel_costs

CALL = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*custom_call_target=\"tpu_custom_call\"",
                  re.M)


def count(step_text: str) -> int:
    return sum(1 for name in CALL.findall(step_text)
               if kernel_costs.FLASH_ATTENTION_OPS.search(name))


def read(run):
    return None if run.step_text is None else count(run.step_text)
