"""From a profiler trace (``.xplane.pb``) to busy time, idle share, the top
device operations and the longest idle gaps.  Reads the file with
``jax.profiler.ProfileData`` and nothing else.

What the reduction takes as "the device ran an operation": events on a
device plane's ``XLA Ops`` line (one event per executed HLO operation, with
start and duration in nanoseconds).  Busy time is the UNION of those
intervals per device, averaged over the devices that ran anything; the window
is the span from the first to the last device event of any device.

Operations nest on that line (a ``while`` spans the operations of its body),
so an operation's time is its SELF time: its duration less the events
directly inside it.  An event's name is the HLO text of the operation; the
short name is the part before `` = `` (``%fusion.2666``, ``%_fwd_impl.3``).
A Mosaic (Pallas) kernel is an operation whose text says
``custom_call_target="tpu_custom_call"``; kernels are grouped by short name without its numeric
suffix (today ``%_fwd_impl``, ``%_bwd_impl`` for the flash kernels, the Python
function's name, and ``%tpu_custom_call`` for every kernel that has none).
The ``device_ops`` of a breakdown are such groups too: 36 layers spread one
fusion over 36 numbered names, and the group is what a reader can act on.
"""

import re

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: host annotations the runners write around their own calls
ANNOTATION_PREFIX = "bench."


def find_xplane(directory: str):
  files = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                           recursive=True))
  return files[-1] if files else None


def load_events(path: str) -> dict:
  """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "host": [...]}``;
  host events are only the runners' own annotations."""
  from jax.profiler import ProfileData
  data = ProfileData.from_file(path)
  devices, host = {}, []
  for plane in data.planes:
    if plane.name.startswith(DEVICE_PLANE_PREFIX):
      ops = []
      for line in plane.lines:
        if line.name != OPS_LINE:
          continue
        for ev in line.events:
          ops.append((short_name(ev.name), float(ev.start_ns),
                      float(ev.duration_ns), KERNEL_MARK in ev.name))
      if ops:
        devices[plane.name] = ops
    elif plane.name.startswith("/host:"):
      for line in plane.lines:
        for ev in line.events:
          if ev.name.startswith(ANNOTATION_PREFIX):
            host.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
  return dict(devices=devices, host=host)


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def short_name(hlo_text: str) -> str:
  return hlo_text.split(" = ", 1)[0]


def kernel_group(short: str) -> str:
  return re.sub(r"[.\d]+$", "", short)


def self_times(ops):
  """``[(name, self_ns, is_kernel)]`` for nested ``(name, start, dur,
  is_kernel)`` events of one line."""
  out, stack = [], []
  for name, s, d, k in sorted(ops, key=lambda e: (e[1], -e[2])):
    while stack and stack[-1][0] <= s:
      stack.pop()
    item = [s + d, name, d, k]
    if stack:
      stack[-1][2] -= d
    stack.append(item)
    out.append(item)
  return [(name, max(0.0, self_ns), k) for _, name, self_ns, k in out]


def union_length(intervals) -> float:
  """Total length covered by ``(start, end)`` intervals."""
  total, cur_s, cur_e = 0.0, None, None
  for s, e in sorted(intervals):
    if cur_e is None or s > cur_e:
      if cur_e is not None:
        total += cur_e - cur_s
      cur_s, cur_e = s, e
    elif e > cur_e:
      cur_e = e
  if cur_e is not None:
    total += cur_e - cur_s
  return total


def gaps(intervals, lo: float, hi: float):
  """The uncovered stretches of ``[lo, hi]`` as ``(start, end)``."""
  out, edge = [], lo
  for s, e in sorted(intervals):
    if s > edge:
      out.append((edge, min(s, hi)))
    edge = max(edge, e)
    if edge >= hi:
      break
  if edge < hi:
    out.append((edge, hi))
  return out


def _label(gap, host) -> str:
  """What the host was doing in a gap: the annotation that covers most of
  it, else ``unattributed``."""
  best, best_cover = "unattributed", 0.0
  for name, s, d in host:
    cover = min(gap[1], s + d) - max(gap[0], s)
    if cover > best_cover:
      best, best_cover = name[len(ANNOTATION_PREFIX):], cover
  return best


def reduce_events(events: dict, top: int = 10, default_gap_label=None):
  """The summary every device metric reads::

      {busy_s, window_s, idle_share, devices, kernels: {group: {seconds,
       calls}}, op_seconds: {short name: self seconds} (the 50 largest),
       op_group_seconds: {short name without its number: self seconds},
       device_ops: [[name, s]..top], idle_gaps: [[label, s]..top]}

  or ``None`` when no device operation was traced."""
  devs = events["devices"]
  if not devs:
    return None
  lo = min(e[1] for ops in devs.values() for e in ops)
  hi = max(e[1] + e[2] for ops in devs.values() for e in ops)
  busy, op_seconds, groups, kernels = [], {}, {}, {}
  for ops in devs.values():
    busy.append(union_length([(e[1], e[1] + e[2]) for e in ops]))
    for name, self_ns, is_kernel in self_times(ops):
      sec = self_ns / 1e9 / len(devs)
      op_seconds[name] = op_seconds.get(name, 0.0) + sec
      group = kernel_group(name)
      groups[group] = groups.get(group, 0.0) + sec
      if is_kernel:
        k = kernels.setdefault(kernel_group(name), dict(seconds=0.0, calls=0))
        k["seconds"] += self_ns / 1e9 / len(devs)
        k["calls"] += 1.0 / len(devs)
  first = next(iter(devs.values()))
  labelled = {}
  for g in gaps([(e[1], e[1] + e[2]) for e in first], lo, hi):
    label = _label(g, events["host"])
    if label == "unattributed" and default_gap_label:
      label = default_gap_label
    labelled.setdefault(label, []).append((g[1] - g[0]) / 1e9)
  longest = sorted(((lab, max(v)) for lab, v in labelled.items()),
                   key=lambda kv: -kv[1])
  window_s = (hi - lo) / 1e9
  busy_s = sum(busy) / len(busy) / 1e9
  return dict(
      busy_s=busy_s, window_s=window_s,
      idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
      devices=len(devs), kernels=kernels,
      op_seconds=dict(sorted(op_seconds.items(),
                             key=lambda kv: -kv[1])[:50]),
      idle_gap_seconds={k: sum(v) for k, v in labelled.items()},
      op_group_seconds=dict(sorted(groups.items(), key=lambda kv: -kv[1])),
      device_ops=[[n, s] for n, s in sorted(
          groups.items(), key=lambda kv: -kv[1])[:top]],
      idle_gaps=[[lab, s] for lab, s in longest[:top]])


def idle_share_percent(report):
  """``device_idle_share.*``: 1 - busy / traced span, in percent."""
  summary = report.get("trace_summary")
  if not summary or summary.get("idle_share") is None:
    return None
  return 100.0 * summary["idle_share"]


def reduce_directory(directory: str, **kw):
  path = find_xplane(directory)
  if path is None:
    return None
  return reduce_events(load_events(path), **kw)
