"""One module per kind of traffic: ``drivers/<kind>.py`` with ``run(job)``."""
