"""One reader per file: ``read(facts, **parameters)`` takes a metric from
the facts of one run (the window's counts and clock, pvar deltas, the
traced slice) and returns a number, or None where it finds nothing to
read. A share of a peak is never returned as 0 for "nothing"."""
