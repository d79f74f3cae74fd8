"""Cell runners: one module per traffic kind."""
