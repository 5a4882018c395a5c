"""model step, serving: the pass at which the looped model's exit gates let
a decoded token go, averaged over the live lanes' tokens: d
``loop_exit_pass_sum`` / d ``live_slot_steps``, the program's counters
(``step_many`` sums, over LIVE lanes, the exit pass that the gates and the
configuration's ``early_exit_threshold`` give).  At the published threshold
1.0 every token leaves at the last pass: 4.0, and a step streams the layers'
weights four times; a lower reading would mean fewer passes were needed.  A
program without the counter reads nothing."""

from benchmarks.lib import needs_ouro as needs


def read(report):
  d = needs.counters(report)
  if d is None:
    return None
  return d["loop_exit_pass_sum"] / d["live_slot_steps"]
