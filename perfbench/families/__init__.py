"""One module per model family: builds the program's model from a
configuration file's keys and hands its weights to the plain reference."""
