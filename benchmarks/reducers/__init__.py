"""One module per way of reading a per-layer metric; a metric file names its reducer."""
