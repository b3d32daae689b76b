"""The yardstick: everything the benchmark needs besides the program under test."""
