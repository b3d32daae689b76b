"""One module per way of driving the program; a traffic file names its driver."""
