"""Adapters from the harness to a system under test.  A configuration
names its system by the ``system`` key; the module of that name here
builds it."""
