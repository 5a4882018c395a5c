"""launcher and compile: seconds inside backend compilation (a load from the
persistent cache counts: it is what the run paid), whole process."""


def read(report):
  return report["compile"]["compile_s"]
