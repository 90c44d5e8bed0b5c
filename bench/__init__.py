"""The on-chip benchmark of the streaming KWS server (see ``run.py``)."""
