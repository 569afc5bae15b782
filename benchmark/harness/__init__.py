"""The benchmark's yardstick: loader, traffic generator, closed-loop driver,
verification, trace reduction, peaks and cost functions.  From the program it
takes only the system under test and its spans, counters and kernel names."""
