"""The on-chip benchmark's yardstick: cell resolution, traffic, trace
reduction, work counts, peaks and the float64 references.  Nothing here is
imported by the program under test."""
