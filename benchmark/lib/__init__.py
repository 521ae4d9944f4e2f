"""The yardstick: window loop, trace reduction, peaks, comparison, last line."""
