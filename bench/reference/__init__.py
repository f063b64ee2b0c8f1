"""Plain references that decide `correct`. They import nothing of the
program and take nothing it made."""
