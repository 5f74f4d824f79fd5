"""Plain references the benchmark compares the system under test with.

A configuration names its reference by the ``reference`` key; the module
of that name here holds it."""
