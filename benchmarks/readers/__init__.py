"""One module per reader; a per-layer metric's file names its reader and
gives it its arguments.  ``read(args, outcome, peaks)`` returns the number,
or None where it finds nothing to read (the metric is then left out of the
result line — never a 0 for a share)."""
