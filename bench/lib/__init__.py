"""The benchmark's yardstick: traffic, weights, serving driver, trace
reduction, operation counts and peaks. Nothing here is imported by the
program under test, and later program changes cannot move it."""
