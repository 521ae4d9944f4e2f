"""One module per model family: how the program builds it, its traffic,
its operations per token and its plain reference."""
