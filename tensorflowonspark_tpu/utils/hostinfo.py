"""Host-level utilities: IP discovery, port selection, executor-id persistence.

Capability parity with the reference's ``util.py``
(/root/reference/tensorflowonspark/util.py:52-94): ``get_ip_address`` (UDP-connect
trick), ``find_in_path``, and the executor-id file protocol that lets transient
data-feeding tasks locate the persistent per-host feed hub started by an earlier
task in the same working directory.
"""

import errno
import os
import socket
import logging

logger = logging.getLogger(__name__)

EXECUTOR_ID_FILE = "executor_id"


def get_ip_address() -> str:
  """Best-effort externally-routable IP of the current host.

  Uses the UDP-connect trick (no packets are actually sent); falls back to
  hostname resolution and finally loopback so single-host/dev environments
  (no network egress) still work.
  """
  try:
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
      s.connect(("8.8.8.8", 80))
      return s.getsockname()[0]
    finally:
      s.close()
  except OSError:
    try:
      return socket.gethostbyname(socket.getfqdn())
    except OSError:
      return "127.0.0.1"


def get_free_port(host: str = "") -> int:
  """Bind an ephemeral TCP port, release it, and return its number."""
  s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
  try:
    s.bind((host, 0))
    return s.getsockname()[1]
  finally:
    s.close()


def find_in_path(path: str, file_name: str):
  """Find a file in a ':'-separated path string; return full path or False."""
  for p in path.split(os.pathsep):
    candidate = os.path.join(p, file_name)
    if os.path.exists(candidate) and os.path.isfile(candidate):
      return candidate
  return False


def single_node_env(num_chips: int = 0, worker_index: int = 0,
                    workers_per_host: int = 1) -> None:
  """Prepare this process's env for standalone single-node execution.

  Parity with the reference's ``util.single_node_env`` (util.py:21-49,
  which expanded the Hadoop classpath and set GPU visibility for one-off
  tasks): on TPU the equivalent is claiming a chip share for this process
  before any JAX/libtpu initialization.
  """
  from tensorflowonspark_tpu.utils import tpu_info
  tpu_info.claim_chips(num_chips, worker_index, workers_per_host,
                       what="single_node_env")


def write_executor_id(num: int, working_dir: str = ".") -> None:
  """Persist this executor's id to a file in the executor working dir.

  Later tasks scheduled onto the same executor (e.g. data-feeding tasks) read
  this file to find the feed hub owned by this executor (reference:
  util.py:77-94, consumed at TFSparkNode.py:482,614).
  """
  with open(os.path.join(working_dir, EXECUTOR_ID_FILE), "w") as f:
    f.write(str(num))


def read_executor_id(working_dir: str = ".") -> int:
  """Read the executor id written by :func:`write_executor_id`."""
  path = os.path.join(working_dir, EXECUTOR_ID_FILE)
  try:
    with open(path, "r") as f:
      return int(f.read())
  except OSError as e:
    if e.errno == errno.ENOENT:
      raise RuntimeError(
          "No executor_id file found in {}; the node runtime has not started "
          "on this executor".format(os.path.abspath(working_dir)))
    raise
