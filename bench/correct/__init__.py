"""The comparison that decides ``correct``, and the plain references."""
