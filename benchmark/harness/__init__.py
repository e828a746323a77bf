"""The yardstick: window, traffic, trace reduction, peaks, kernel work,
weights, plain references and the comparisons that decide ``correct``."""
