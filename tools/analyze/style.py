"""Style pass of the analysis suite (the former ``tools/lint.py``, folded in).

Stdlib-only (ast + tokenize); the image ships no pycodestyle/pyflakes and
installs are impossible. Checks:

- E9: syntax errors (files must compile)
- W291/W293: trailing whitespace
- E501: lines over 100 chars
- W191: tabs in indentation
- F401: imported name never used (module scope; ``# noqa`` honored)
- F811: duplicate top-level definition names
- F841: local variable assigned but never used
- W605: invalid escape sequence in a non-raw string literal
- E722: bare ``except:``
- B006: mutable default arguments

``python tools/lint.py`` remains a thin shim over this module so existing
muscle memory and Makefile references keep working.
"""

import ast
import io
import os
import re
import sys
import tokenize

MAX_LINE = 100

DEFAULT_PATHS = ["tensorflowonspark_tpu", "tests", "examples",
                 "__graft_entry__.py", "tools/analyze", "tools/lint.py"]

# python's recognized escapes (str); bytes additionally lack N/u/U
_VALID_ESCAPES = set("\n\\'\"abfnrtv01234567x")
_STR_ESCAPES = _VALID_ESCAPES | set("NuU")


def _noqa_lines(source):
  """Line numbers carrying a ``# noqa`` comment (any code)."""
  out = set()
  try:
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
      if tok.type == tokenize.COMMENT and "noqa" in tok.string:
        out.add(tok.start[0])
  except tokenize.TokenizeError:
    pass
  return out


class _ImportTracker(ast.NodeVisitor):
  """Module-scope imports vs every name used anywhere in the module."""

  def __init__(self):
    self.imports = {}   # name -> lineno
    self.used = set()

  def visit_Import(self, node):
    for a in node.names:
      name = (a.asname or a.name).split(".")[0]
      self.imports[name] = node.lineno
    self.generic_visit(node)

  def visit_ImportFrom(self, node):
    for a in node.names:
      if a.name == "*":
        continue
      self.imports[a.asname or a.name] = node.lineno
    self.generic_visit(node)

  def visit_Name(self, node):
    self.used.add(node.id)
    self.generic_visit(node)

  def visit_Attribute(self, node):
    self.generic_visit(node)


def _check_unused_locals(tree, noqa, path, findings):
  """F841: simple assignments whose name is never read in the function."""
  for func in ast.walk(tree):
    if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
      continue
    assigns = {}   # name -> first assign lineno
    loads = set()
    declared = set()   # global/nonlocal: writes are visible outside
    # assignments: this function's own scope only (nested defs/classes have
    # their own scopes — a class attribute is not a local variable)
    stack = list(func.body)
    while stack:
      node = stack.pop()
      if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef, ast.Lambda)):
        continue
      if isinstance(node, (ast.Global, ast.Nonlocal)):
        declared.update(node.names)
      elif isinstance(node, ast.Assign):
        # only simple single-name targets (pyflakes convention: tuple
        # unpacking and attribute/subscript stores are not F841)
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
          name = node.targets[0].id
          assigns[name] = min(assigns.get(name, node.lineno), node.lineno)
      stack.extend(ast.iter_child_nodes(node))
    # loads: anywhere inside, including nested functions (closures)
    for node in ast.walk(func):
      if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
        loads.add(node.id)
    for name, lineno in sorted(assigns.items(), key=lambda kv: kv[1]):
      if name.startswith("_") or name in loads or name in declared:
        continue
      if lineno in noqa:
        continue
      findings.append((path, lineno,
                       "F841 local variable %r assigned but never used"
                       % name))


def _check_escapes(source, noqa, path, findings):
  """W605: invalid escape sequences in non-raw string literals."""
  try:
    toks = list(tokenize.generate_tokens(io.StringIO(source).readline))
  except (tokenize.TokenizeError, IndentationError):
    return
  for tok in toks:
    if tok.type != tokenize.STRING:
      continue
    text = tok.string
    prefix = re.match(r"[A-Za-z]*", text).group(0).lower()
    if "r" in prefix:
      continue
    valid = _VALID_ESCAPES if "b" in prefix else _STR_ESCAPES
    body = text[len(prefix):]
    quote = body[:3] if body[:3] in ('"""', "'''") else body[:1]
    body = body[len(quote):-len(quote)] if len(body) >= 2 * len(quote) else ""
    i = 0
    reported = set()
    while i < len(body) - 1:
      if body[i] == "\\":
        nxt = body[i + 1]
        if nxt not in valid and nxt not in reported:
          line = tok.start[0]
          if line not in noqa:
            findings.append((path, line,
                             "W605 invalid escape sequence '\\%s'" % nxt))
          reported.add(nxt)
        i += 2
        continue
      i += 1


def _check_ast(path, tree, source, findings):
  noqa = _noqa_lines(source)
  is_init = os.path.basename(path) == "__init__.py"

  tracker = _ImportTracker()
  tracker.visit(tree)
  if not is_init:
    exported = source.split("__all__", 1)[1] if "__all__" in source else ""
    for name, lineno in sorted(tracker.imports.items(), key=lambda kv: kv[1]):
      if name not in tracker.used and name != "_" and lineno not in noqa \
          and name not in exported:
        findings.append((path, lineno, "F401 %r imported but unused" % name))

  seen_defs = {}
  for node in tree.body:
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
      if node.name in seen_defs and node.lineno not in noqa:
        findings.append((path, node.lineno,
                         "F811 redefinition of %r (first at line %d)"
                         % (node.name, seen_defs[node.name])))
      seen_defs[node.name] = node.lineno

  for node in ast.walk(tree):
    if isinstance(node, ast.ExceptHandler) and node.type is None \
        and node.lineno not in noqa:
      findings.append((path, node.lineno, "E722 bare 'except:'"))
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
      for default in list(node.args.defaults) + \
          [d for d in node.args.kw_defaults if d is not None]:
        if isinstance(default, (ast.List, ast.Dict, ast.Set)) \
            and default.lineno not in noqa:
          findings.append((path, default.lineno,
                           "B006 mutable default argument"))

  _check_unused_locals(tree, noqa, path, findings)
  _check_escapes(source, noqa, path, findings)


def _check_text(path, source, findings):
  noqa = _noqa_lines(source)
  for i, line in enumerate(source.splitlines(), 1):
    if i in noqa:
      continue
    stripped = line.rstrip("\n")
    if stripped != stripped.rstrip():
      findings.append((path, i, "W291 trailing whitespace"))
    if len(stripped) > MAX_LINE and "http" not in stripped:
      findings.append((path, i, "E501 line too long (%d > %d)"
                       % (len(stripped), MAX_LINE)))
    body = stripped[:len(stripped) - len(stripped.lstrip())]
    if "\t" in body:
      findings.append((path, i, "W191 tab in indentation"))


def lint_file(path, findings):
  with open(path, encoding="utf-8") as f:
    source = f.read()
  try:
    tree = ast.parse(source, filename=path)
  except SyntaxError as e:
    findings.append((path, e.lineno or 0, "E9 syntax error: %s" % e.msg))
    return
  _check_text(path, source, findings)
  _check_ast(path, tree, source, findings)


def collect_py_files(roots):
  # one walker for both passes: the TOS rules and the style pass must
  # never disagree about which files exist
  from tools.analyze import engine
  return sorted(engine.collect_files(list(roots)))


def run_style(paths=None, cache_path=None):
  """Lint the given paths (or the defaults); returns (files, findings).

  ``cache_path``: reuse per-file results keyed on content digest (see
  tools/analyze/cache.py; ``make analyze-cold`` bypasses it).
  """
  files = collect_py_files(paths or DEFAULT_PATHS)
  if cache_path is not None:
    from tools.analyze import cache
    return files, cache.style_pass(files, cache_path, lint_file)
  findings = []
  for path in files:
    lint_file(path, findings)
  return files, findings


def main(argv):
  files, findings = run_style(argv[1:] or None)
  for path, lineno, msg in findings:
    print("%s:%d: %s" % (path, lineno, msg))
  print("lint: %d file(s), %d finding(s)" % (len(files), len(findings)))
  return 1 if findings else 0


if __name__ == "__main__":
  sys.exit(main(sys.argv))
