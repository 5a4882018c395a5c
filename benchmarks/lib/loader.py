"""Finding a family, a runner or a per-layer reader by the name a data file
gives it."""

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def load_module(kind: str, name: str):
  """``benchmarks/<kind>/<name>.py`` as a module (a metric's name may hold
  dots and dashes, so by path)."""
  path = os.path.join(HERE, kind, name + ".py")
  if not os.path.exists(path):
    raise FileNotFoundError("no %s named %r (%s)" % (kind, name, path))
  mod_name = "benchmarks.%s.%s" % (
      kind, name.replace(".", "_").replace("-", "_"))
  if mod_name in sys.modules:
    return sys.modules[mod_name]
  spec = importlib.util.spec_from_file_location(mod_name, path)
  mod = importlib.util.module_from_spec(spec)
  sys.modules[mod_name] = mod
  spec.loader.exec_module(mod)
  return mod


def load_json(path: str) -> dict:
  with open(path) as f:
    return json.load(f)
