"""One module per standing-query app: the benchmark's own plain reference
of that app (copied from the program's ``core/reference.py``) and the
number that compares a read-back answer with it.  Each module gives
``NAME`` (what the number counts), ``reference(n, edges, source)``,
``reference_bfloat16`` (the same reference computed in bfloat16, for the
control of a configuration that states float32) and
``compare(got, want) -> float``; the configuration file gives the limit."""
